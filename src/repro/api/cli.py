"""``python -m repro`` — the single entry point over the scenario API.

Commands:

  list                       table of registered scenarios
  show NAME                  print a scenario's JSON spec
  run NAME|--spec FILE       run a scenario, print metrics (or --json)
  sweep NAME --grid k=v1,v2  grid sweep over dotted-path overrides
  sweep NAME --samples N     Monte-Carlo fleet sweep (versioned artifact)
  replay TRACE.jsonl         offline detect/mitigate over a recorded trace
  monitor NAME|--trace FILE  run with metrics + alert rules (or evaluate
                             the rules offline over a recorded trace) and
                             emit dashboards / incident timelines
  lint [PATHS...]            check the repo's determinism / replay /
                             engine-parity invariants (repro.analysis)

Exit codes: 0 success, 1 runtime failure (for ``lint``: findings or stale
baseline entries), 2 unknown scenario / bad usage (matching
``benchmarks/run.py --only``).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

from repro.api.registry import get_scenario, list_scenarios, variants
from repro.api.runner import run_scenario
from repro.api.spec import Scenario, TelemetrySpec, parse_set_arg, \
    with_overrides


def _load_scenario(args) -> Scenario:
    """Resolve NAME / --spec into a Scenario; SystemExit(2) on unknown."""
    if getattr(args, "spec", None):
        sc = Scenario.load(args.spec)
    else:
        if not args.name:
            print("error: give a scenario NAME or --spec FILE",
                  file=sys.stderr)
            raise SystemExit(2)
        try:
            sc = get_scenario(args.name)
        except KeyError as e:
            print(f"error: {e.args[0]}", file=sys.stderr)
            raise SystemExit(2)
    overrides = dict(parse_set_arg(s) for s in (args.set or []))
    if getattr(args, "engine", None):
        key = "fleet.engine" if sc.fleet is not None else "sim.engine"
        overrides.setdefault(key, args.engine)
    if getattr(args, "seed", None) is not None:
        overrides.setdefault("seed", args.seed)
    if overrides:
        sc = with_overrides(sc, overrides)
    return sc


def _add_scenario_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("name", nargs="?", help="registered scenario name")
    p.add_argument("--spec", help="run a JSON scenario file instead")
    p.add_argument("--iterations", type=int, default=None,
                   help="override the scenario's iteration count")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--engine", choices=["event", "batched", "vector", "jax"],
                   help="override the simulation engine")
    p.add_argument("--set", action="append", metavar="KEY=VALUE",
                   help="dotted-path override, e.g. --set sim.noise=0.01")


def cmd_list(args) -> int:
    rows = list_scenarios()
    if args.json:
        print(json.dumps([{"name": n, "scope": s, "description": d}
                          for n, s, d in rows], indent=2,
                         sort_keys=True, allow_nan=False))
        return 0
    width = max(len(n) for n, _, _ in rows)
    for name, scope, desc in rows:
        print(f"{name:<{width}s}  {scope:<5s}  {desc}")
    return 0


def cmd_show(args) -> int:
    sc = _load_scenario(args)
    print(sc.to_json())
    return 0


def cmd_run(args) -> int:
    sc = _load_scenario(args)
    if (args.save_trace or args.chrome_trace) and sc.telemetry is None:
        sc = sc.replace(telemetry=TelemetrySpec())   # lossless default
    res = run_scenario(sc, iterations=args.iterations,
                       save_trace_path=args.save_trace,
                       chrome_trace_path=args.chrome_trace)
    payload = res.to_json_dict()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True, allow_nan=False)
    if args.json:
        print(json.dumps(payload, indent=2, sort_keys=True,
                         allow_nan=False))
    else:
        from repro.api.reports import format_result
        print(format_result(res))
        if res.trace_path:
            print(f"trace written to {res.trace_path}")
    return 0


def cmd_sweep(args) -> int:
    if args.samples is not None or args.sweep_spec:
        return _cmd_sweep_mc(args)
    sc = _load_scenario(args)
    grid = {}
    for s in args.grid or []:
        key, raw = s.split("=", 1)
        grid[key.strip()] = [parse_set_arg(f"x={v}")[1]
                             for v in raw.split(",")]
    if not grid:
        print("error: sweep needs --samples N (Monte-Carlo), --sweep-spec "
              "FILE, or at least one --grid KEY=V1,V2,...", file=sys.stderr)
        return 2
    rows = []
    for label, variant in variants(sc, grid):
        res = run_scenario(variant, iterations=args.iterations)
        rows.append({"variant": label, **res.metrics})
        if not args.json:
            keys = [k for k in res.metrics
                    if k in ("fleet_tput", "throughput", "detect_accuracy")]
            brief = "  ".join(f"{k}={res.metrics[k]:.4f}" for k in keys)
            print(f"{label:<48s} {brief}")
    if args.json:
        print(json.dumps(rows, indent=2, sort_keys=True, allow_nan=False))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=2, sort_keys=True, allow_nan=False)
    return 0


def _cmd_sweep_mc(args) -> int:
    """Monte-Carlo (or spec-file) sweep → versioned artifact.

    ``--samples N`` builds a default `SweepSpec` over the named scenario
    (per-sample thermal lotteries, plus any ``--dist`` distributions);
    ``--sweep-spec FILE`` loads a full spec instead.  The artifact schema
    is documented in docs/sweeps.md.
    """
    from repro.api.sweep import Dist, SweepSpec, run_sweep
    if args.sweep_spec:
        spec = SweepSpec.load(args.sweep_spec)
        if args.name and args.name != spec.scenario:
            print(f"error: --sweep-spec names scenario "
                  f"{spec.scenario!r}, not {args.name!r}", file=sys.stderr)
            return 2
        if args.samples is not None:
            spec = SweepSpec.from_dict({**spec.to_dict(),
                                        "samples": args.samples})
    else:
        if not args.name:
            print("error: give a scenario NAME (or --sweep-spec FILE)",
                  file=sys.stderr)
            return 2
        dists = {}
        for s in args.dist or []:
            key, raw = s.split("=", 1)
            body = json.loads(raw)
            if not isinstance(body, dict):
                raise ValueError(f"--dist {key}: expected a JSON object "
                                 f"like {{\"kind\":\"uniform\",...}}")
            dists[key.strip()] = Dist(**body)
        spec = SweepSpec(scenario=args.name, samples=args.samples,
                         dists=dists, seed=args.seed or 0,
                         iterations=args.iterations)
    artifact = run_sweep(spec)
    text = json.dumps(artifact, indent=2, sort_keys=True, allow_nan=False)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        s = artifact["summary"]
        print(f"{artifact['scenario']}  mode={artifact['mode']}  "
              f"engine={artifact['engine']}  n={artifact['n_samples']}")
        for name in ("t_fleet_s", "throughput", "lead_max_s", "recovery"):
            q = s[name]
            print(f"  {name:<13s} mean={q['mean']:.5g}  p10={q['p10']:.5g}"
                  f"  p50={q['p50']:.5g}  p90={q['p90']:.5g}")
        if args.out:
            print(f"artifact written to {args.out}")
    return 0


def cmd_replay(args) -> int:
    import numpy as np

    from repro.core.manager import FleetManagerConfig, ManagerConfig
    from repro.telemetry import (detection_report, fleet_lead_report,
                                 load_trace, replay_fleet, replay_node)
    trace = load_trace(args.trace)
    scope = args.scope
    if scope == "auto":
        scope = "fleet" if trace.fleet else "node"
    out = {"trace": args.trace, "scope": scope}
    esc_trace = ("escalation" in (trace.meta or {})
                 or any(e.source == "escalation" for e in trace.events))
    if scope == "fleet" and esc_trace:
        # healing traces change fleet width across drain epochs, so the
        # budget replay does not apply; re-run the escalation decisions
        # instead and check them bit-for-bit against the recording
        from repro.telemetry import (escalation_replay_matches,
                                     replay_escalation)
        rp = replay_escalation(trace)
        mismatches: List[str] = []
        out["escalation_events"] = len(rp.events)
        out["drained_nodes"] = rp.drained_nodes
        out["replay_matches"] = bool(
            escalation_replay_matches(trace, rp, log=mismatches))
        if mismatches:
            out["mismatches"] = mismatches
    elif scope == "fleet":
        cfg = FleetManagerConfig(use_case=args.use_case, sampling_period=2,
                                 warmup=2, window_size=2, node_window_size=2,
                                 power_cap=700.0)
        rp = replay_fleet(trace, cfg, tune_after=args.tune_after or 0)
        out["budget_adjustments"] = len(rp.budget_log)
        out["final_caps"] = np.asarray(rp.final_caps).tolist()
    else:
        cfg = ManagerConfig(use_case=args.use_case, sampling_period=2,
                            warmup=3, window_size=2, power_cap=700.0)
        rp = replay_node(trace, cfg, node=args.node,
                         tune_after=args.tune_after)
        out["cap_adjustments"] = len(rp.cap_schedule)
        out["final_caps"] = np.asarray(rp.final_caps).tolist()
        if args.export_caps:
            rp.export_caps(args.export_caps)
            out["caps_file"] = args.export_caps
    try:
        rep = detection_report(trace, node=args.node)
        out["detect"] = {"accuracy": rep.accuracy,
                         "accuracy_imputed": rep.accuracy_imputed,
                         "lead_rel_error": rep.lead_rel_error,
                         "majority_correct": rep.majority_correct}
    except ValueError:
        pass
    try:
        frep = fleet_lead_report(trace)
        out["fleet_lead"] = {"accuracy": frep.accuracy,
                             "lead_rel_error": frep.lead_rel_error,
                             "majority_correct": frep.majority_correct}
    except ValueError:
        pass
    if args.json:
        print(json.dumps(out, indent=2, sort_keys=True, allow_nan=False))
    else:
        for k, v in out.items():
            print(f"{k}: {v}")
    return 0


def _nanless(obj):
    """JSON-safe copy: NaN/Inf become None (the monitor payload mixes
    score dicts that legally carry NaN)."""
    import math
    if isinstance(obj, dict):
        return {k: _nanless(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_nanless(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def cmd_monitor(args) -> int:
    """Live observability run, or offline rule evaluation over a recorded
    trace.  ``--check-replay`` re-evaluates the rules from the trace and
    exits 1 unless the firings match the recorded ones bit-for-bit."""
    import math

    from repro.api.spec import ObservabilitySpec
    from repro.obs import (alert_replay_matches, render_dashboard,
                           replay_alerts, save_incidents, score_alerts,
                           terminal_summary, transitions_to_records)
    from repro.telemetry import load_trace
    from repro.telemetry.trace_io import TelemetryTrace

    out = {}
    if args.trace:
        trace = load_trace(args.trace)
        pipe = replay_alerts(trace)
        out["trace"] = args.trace
        if not any(e.source == "alert" for e in trace.events):
            if args.check_replay:
                print("error: --check-replay needs a trace recorded with "
                      "observability (no alert rows found)", file=sys.stderr)
                return 2
            # recorded without alert rows (record_alerts off, or a
            # degraded copy): inject the replayed firings so incidents
            # and the dashboard have something to annotate
            trace.events = sorted(
                trace.events + transitions_to_records(pipe.transitions),
                key=lambda e: e.iteration)
    else:
        sc = _load_scenario(args)
        if sc.observability is None:
            sc = sc.replace(observability=ObservabilitySpec())
        if sc.telemetry is None:
            sc = sc.replace(telemetry=TelemetrySpec())
        res = run_scenario(sc, iterations=args.iterations,
                           save_trace_path=args.save_trace)
        trace = TelemetryTrace.from_collector(res.collector)
        pipe = res.obs
        out["scenario"] = sc.name or None
        out["metrics"] = res.metrics
        if args.save_trace:
            out["trace_path"] = args.save_trace
    patience = float((trace.meta.get("escalation") or {}).get(
        "patience_s", math.nan))
    out["transitions"] = len(pipe.transitions)
    out["alerts"] = score_alerts(trace, patience_s=patience)
    if args.check_replay:
        mismatches: List[str] = []
        out["replay_matches"] = bool(
            alert_replay_matches(trace, log=mismatches))
        if mismatches:
            out["mismatches"] = mismatches[:20]
    if args.dashboard:
        render_dashboard(trace, args.dashboard)
        out["dashboard"] = args.dashboard
    if args.incidents:
        save_incidents(trace, args.incidents)
        out["incidents_file"] = args.incidents
    if args.metrics:
        if args.metrics.endswith(".jsonl"):
            pipe.registry.snapshot_jsonl(args.metrics)
        else:
            with open(args.metrics, "w") as f:
                f.write(pipe.registry.exposition())
        out["metrics_file"] = args.metrics
    if args.out:
        with open(args.out, "w") as f:
            json.dump(_nanless(out), f, indent=2, sort_keys=True,
                      allow_nan=False)
    if args.json:
        print(json.dumps(_nanless(out), indent=2, sort_keys=True,
                         allow_nan=False))
    else:
        print(terminal_summary(trace, patience_s=patience))
        for key in ("dashboard", "incidents_file", "metrics_file",
                    "trace_path"):
            if key in out:
                print(f"{key.replace('_file', '')} written to {out[key]}")
    return 1 if out.get("replay_matches") is False else 0


def cmd_lint(args) -> int:
    from pathlib import Path

    from repro.analysis import (RULES, lint_paths, render_json, render_text,
                                update_baseline)
    if args.list_rules:
        for rid in sorted(RULES):
            print(f"{rid}  {RULES[rid].title}")
        return 0
    rules = ([s.strip() for s in args.rules.split(",") if s.strip()]
             if args.rules else None)
    result, baseline = lint_paths(paths=args.paths or None, root=args.root,
                                  rules=rules, baseline_path=args.baseline)
    if args.update_baseline:
        raw = sorted(result.findings + result.suppressed)
        refreshed = update_baseline(baseline, raw)
        path = baseline.path or str(Path(result.root) / "lint_baseline.json")
        refreshed.save(path)
        print(f"baseline rewritten: {path} ({len(refreshed.entries)} "
              f"entr{'y' if len(refreshed.entries) == 1 else 'ies'}; review "
              f"any UNREVIEWED reasons before committing)")
        return 0
    report = render_json(result)
    if args.out:
        with open(args.out, "w") as f:
            f.write(report + "\n")
    if args.json:
        print(report)
    else:
        print(render_text(result))
    return result.exit_code()


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="Lit Silicon scenario runner (see README 'Scenario "
                    "API')")
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("list", help="list registered scenarios")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("show", help="print a scenario's JSON spec")
    _add_scenario_args(p)
    p.set_defaults(fn=cmd_show)

    p = sub.add_parser("run", help="run a scenario and print its metrics")
    _add_scenario_args(p)
    p.add_argument("--json", action="store_true",
                   help="print the result as JSON")
    p.add_argument("--out", help="also write the result JSON to a file")
    p.add_argument("--save-trace", metavar="PATH",
                   help="record + write a telemetry JSONL trace")
    p.add_argument("--chrome-trace", metavar="PATH",
                   help="also write a Perfetto-loadable Chrome trace")
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("sweep",
                       help="grid or Monte-Carlo sweep a scenario")
    _add_scenario_args(p)
    p.add_argument("--grid", action="append", metavar="KEY=V1,V2,...",
                   help="dotted-path grid axis (repeatable)")
    p.add_argument("--samples", type=int, default=None, metavar="N",
                   help="Monte-Carlo mode: N samples over the fleet "
                        "distributions (emits a sweep artifact)")
    p.add_argument("--dist", action="append", metavar="KEY=JSON",
                   help="Monte-Carlo distribution for a dotted path, e.g. "
                        "--dist fleet.straggler_boost="
                        "'{\"kind\":\"uniform\",\"low\":1.1,\"high\":1.5}'")
    p.add_argument("--sweep-spec", metavar="FILE",
                   help="load a full SweepSpec JSON instead of --samples")
    p.add_argument("--json", action="store_true")
    p.add_argument("--out", help="write rows / sweep artifact JSON to FILE")
    p.set_defaults(fn=cmd_sweep)

    p = sub.add_parser("replay",
                       help="offline detect/mitigate over a recorded trace")
    p.add_argument("trace", help="telemetry JSONL file (save_trace output)")
    p.add_argument("--scope", choices=["auto", "node", "fleet"],
                   default="auto")
    p.add_argument("--use-case", default="gpu-realloc")
    p.add_argument("--tune-after", type=int, default=None)
    p.add_argument("--node", type=int, default=0)
    p.add_argument("--export-caps", metavar="PATH",
                   help="write the replayed converged caps file")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_replay)

    p = sub.add_parser("monitor",
                       help="run with the observability pipeline, or "
                            "evaluate alert rules offline over a trace")
    _add_scenario_args(p)
    p.add_argument("--trace", metavar="FILE",
                   help="offline mode: evaluate the rules over this "
                        "recorded telemetry JSONL instead of running")
    p.add_argument("--check-replay", action="store_true",
                   help="verify offline rule evaluation reproduces the "
                        "recorded alert firings bit-for-bit (exit 1 on "
                        "mismatch)")
    p.add_argument("--dashboard", metavar="PATH",
                   help="write the HTML fleet-health dashboard")
    p.add_argument("--incidents", metavar="PATH",
                   help="write the incident timeline JSONL")
    p.add_argument("--metrics", metavar="PATH",
                   help="write the metrics snapshot (Prometheus text, or "
                        "JSONL when PATH ends in .jsonl)")
    p.add_argument("--save-trace", metavar="PATH",
                   help="record + write the telemetry JSONL trace "
                        "(live mode)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--out", help="also write the JSON payload to a file")
    p.set_defaults(fn=cmd_monitor)

    p = sub.add_parser("lint",
                       help="check the repo's determinism / replay / "
                            "engine-parity invariants (static analysis)")
    p.add_argument("paths", nargs="*",
                   help="files or directories to lint (default: src/repro, "
                        "scripts, benchmarks, examples under the repo root)")
    p.add_argument("--root", help="repo root for scope/baseline path "
                                  "resolution (default: auto-detected)")
    p.add_argument("--baseline", metavar="FILE|none",
                   help="baseline file of reviewed, accepted findings "
                        "(default: <root>/lint_baseline.json if present; "
                        "'none' disables suppression)")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline from the current findings: "
                        "keep still-matching entries, drop stale ones, add "
                        "UNREVIEWED entries for new findings")
    p.add_argument("--rules", metavar="CSV",
                   help="comma-separated rule ids to run (default: all)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--json", action="store_true",
                   help="print the JSON report instead of text")
    p.add_argument("--out", help="also write the JSON report to a file")
    p.set_defaults(fn=cmd_lint)
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    try:
        return args.fn(args)
    except SystemExit as e:                      # _load_scenario usage errors
        return int(e.code or 0)
    except (ValueError, KeyError, FileNotFoundError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    except Exception as e:                       # genuine runtime failure
        print(f"error: {type(e).__name__}: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
