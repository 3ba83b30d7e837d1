#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: its entry in BENCHMARK.json
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``) and has a file of its own
(``bench/cells/<cell>.json``) with the limits of its correctness checks;
the configuration names the driver module that
runs it (``bench/drivers/<driver>.py``); every metric is a reader of its own
(``bench/metrics/<metric>.py``).  Adding a cell or a metric adds files and
BENCHMARK.json entries and edits none.

A run: turn on the persistent compile cache, require a TPU with the cell's
chip count (no result and a non-zero exit otherwise), let the driver module build
inputs and weights from ``--seed`` and warm up every shape (``setup_s``),
measure for ``--seconds`` (with ``--trace 1`` the first ``trace_units``
units of work, as the traffic file gives them, run under the profiler, each
in a ``StepTraceAnnotation``: few enough that the device trace keeps every
event), read the peak device memory, let the
driver compare what the timed path produced with the plain reference, and
print one JSON line last on standard output.  The compared numbers go last on
standard error too, and last in the JSON line under ``checks``.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()          # before the heavy imports: set-up starts here

import argparse                  # noqa: E402
import contextlib                # noqa: E402
import json                      # noqa: E402
import math                      # noqa: E402
import shutil                    # noqa: E402
import sys                       # noqa: E402
import traceback                 # noqa: E402
from pathlib import Path         # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.common import (BENCH, CompileClock, NoChip, load_json,  # noqa: E402
                          load_module, peaks, require_tpu)

TRACE_DIR = BENCH / "out" / "trace"


class Run:
    """What one run measured, handed to every metric reader."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = seed, seconds
        self.records: list = []       # one dict per unit of work in the window
        self.setup_s = math.nan
        self.window_s = math.nan      # window start to the last completion
        self.window_compiles = 0
        self.devices: list = []
        self.peaks: dict = {}
        self.trace = None             # bench.xplane.Trace of a --trace 1 run


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def metric_specs(bench: dict, cell: str, trace: bool) -> list:
    """The metrics this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metrics(run: Run, specs: list) -> dict:
    out = {}
    for m in specs:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def window(run: Run, driver, seconds: float, trace_units: int,
           logdir: Path) -> None:
    """Drive the timed path for ``seconds``: units of work back to back,
    each started while the window is open, each timed to its end.  The
    first ``trace_units`` of them run under the profiler."""
    import jax
    if trace_units:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # a span per Python call costs
        jax.profiler.start_trace(str(logdir), profiler_options=options)
    t0 = time.perf_counter()
    k = 0
    with CompileClock() as clock:
        while k < trace_units or time.perf_counter() - t0 < seconds:
            start = time.perf_counter() - t0
            span = (jax.profiler.StepTraceAnnotation(driver.span, step_num=k)
                    if k < trace_units else contextlib.nullcontext())
            with span:
                work = driver.step(k)
            run.records.append({"start": start,
                                "end": time.perf_counter() - t0, **work})
            k += 1
            if k == trace_units:
                jax.profiler.stop_trace()
    run.window_s = run.records[-1]["end"]
    run.window_compiles = clock.compiles


def peak_bytes(device) -> int:
    """The device's peak: its buffers' peak plus the peak of the space the
    runtime reserves for the programs' temporaries, which
    ``peak_bytes_in_use`` leaves out (a train step's gradients and
    activations)."""
    stats = device.memory_stats()
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def run_cell(name: str, cell: dict, config: dict, traffic: dict,
             limits: dict, seed: int, seconds: float, trace: bool,
             devices: list, bench: dict) -> dict:
    """One run of a cell on ``devices``: set-up, window, metrics, checks;
    returns the result line's object."""
    import jax
    run = Run(cell, config, traffic, seed, seconds)
    run.devices = devices
    run.peaks = peaks(devices[0].device_kind) if trace else {}
    driver_mod = load_module(BENCH / "drivers" / f"{config['driver']}.py",
                             f"bench_driver_{config['driver']}")
    driver = driver_mod.Driver(config, traffic, seed, devices)
    driver.setup()

    logdir = TRACE_DIR / name
    if trace:
        shutil.rmtree(logdir, ignore_errors=True)
    run.setup_s = time.time() - T_PROCESS
    window(run, driver, seconds, int(traffic["trace_units"]) if trace else 0,
           logdir)
    if trace:
        from bench.xplane import Trace
        run.trace = Trace.load(logdir, [d.id for d in devices], driver.span)
        shutil.rmtree(logdir, ignore_errors=True)

    memory_peak = max(peak_bytes(d) for d in devices) \
        if devices[0].platform == "tpu" else 0
    metrics = read_metrics(run, metric_specs(bench, name, trace))
    checks = driver.verify()
    for c in checks:
        c["limit"] = limits[c["name"]]
    failed = sum(1 for r in run.records if r.get("failed"))
    correct = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": len(run.records),
              "failed": failed, "metrics": metrics, "device": device,
              "window_compiles": run.window_compiles}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be a whole number >= 0")

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = cell_entry(bench, args.workload)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "cells" / f"{args.workload}.json")["limits"]

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    try:
        devices = require_tpu(int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    peaks(devices[0].device_kind)              # a missing kind is an error
    result = run_cell(args.workload, cell, config, traffic, limits,
                      args.seed, args.seconds, bool(args.trace), devices,
                      bench)
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(f"correct {result['correct']} attempted {result['attempted']} "
          f"failed {result['failed']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)
