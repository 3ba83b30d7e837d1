"""Driver of the fleet-sweep cells: whole ``run_sweep(SweepSpec)`` calls,
spec to artifact, on a registered fleet scenario set up as the
configuration file states.

Set-up registers the scenario with its thermal-lottery seed taken from the
run's seed, checks that the program's scenario is the configuration, and
runs one sweep of the window's shape (compiling it, or loading it from the
persistent cache).  Each unit of work in the window is one sweep with a
sweep seed of its own.  ``verify`` runs the plain reference
(``bench/reference/fleet.py``) over samples of the last sweep drawn from the
seed, and the healthy reference row, once the window has closed.
"""
from __future__ import annotations

import math

import numpy as np

METRICS = ("t_fleet_s", "throughput", "lead_max_s", "fleet_power_w")


def scenario_mismatches(sc, cfg: dict) -> list:
    """Where the program's scenario departs from the configuration file."""
    from repro.configs import get_config
    from repro.core.thermal import PRESETS

    model = get_config(sc.workload.arch)
    have = {
        "arch": sc.workload.arch, "num_hidden_layers": sc.workload.n_layers,
        "batch": sc.workload.batch, "seq": sc.workload.seq,
        "n_shards": sc.workload.n_shards, "hidden_size": model.d_model,
        "intermediate_size": model.d_ff,
        "num_attention_heads": model.n_heads,
        "num_key_value_heads": model.n_kv_heads, "head_dim": model.head_dim,
        "devices_per_node": sc.node.devices, "caps_w": sc.node.caps_w,
        "preset_name": sc.node.preset, "n_nodes": sc.fleet.n_nodes,
        "topology": sc.fleet.topology,
        "inter_node_gbps": sc.fleet.inter_node_gbps,
        "straggler_node": sc.fleet.straggler_node,
        "straggler_boost": sc.fleet.straggler_boost,
        "healthy_boost": sc.fleet.healthy_boost,
    }
    bad = [f"{k}: program {v!r}, configuration {cfg[k]!r}"
           for k, v in have.items() if v != cfg[k]]
    preset = PRESETS[sc.node.preset]
    bad += [f"preset.{k}: program {getattr(preset, k)!r}, configuration {v!r}"
            for k, v in cfg["preset"].items() if getattr(preset, k) != v]
    bad += [f"sim.{k}: program {getattr(sc.sim, k)!r}, configuration {v!r}"
            for k, v in cfg["sim"].items() if getattr(sc.sim, k) != v]
    for what, v in (("fleet.churn", sc.fleet.churn),
                    ("fleet.node_presets", sc.fleet.node_presets),
                    ("fleet.grad_bytes", sc.fleet.grad_bytes)):
        if v is not None:
            bad.append(f"{what}: program {v!r}, configuration None")
    return bad


def rel_gap(a: float, b: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


def verify_picks(n: int, k: int, seed: int) -> list:
    """``k`` sample indices of ``0..n-1`` drawn from the seed, one from each
    of ``k`` equal strata, so that every stretch of ``n / k`` samples or more
    has one compared."""
    k = min(k, n)
    rng = np.random.default_rng([seed, 0x5EE9])
    return [int(rng.integers(j * n // k, (j + 1) * n // k)) for j in range(k)]


class Driver:
    span = "sweep"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.name = (f"bench/{config['scenario']}-{config['n_nodes']}n"
                     f"-seed{seed}")
        self.last = None                  # (sweep seed, artifact)

    def sweep_seed(self, k: int) -> int:
        """Sweep ``k`` of the window; -1 is the warm-up sweep."""
        return self.seed * 1000 + k + 1

    def spec(self, k: int):
        from repro.api.sweep import SweepSpec
        return SweepSpec(scenario=self.name, samples=self.traffic["samples"],
                         seed=self.sweep_seed(k),
                         iterations=self.traffic["iterations"])

    def setup(self) -> None:
        from repro.api.registry import get_scenario, register, scenario_names
        from repro.api.spec import with_overrides

        cfg, name, seed = self.cfg, self.name, self.seed

        def scenario():
            sc = with_overrides(get_scenario(cfg["scenario"]),
                                {"fleet.n_nodes": cfg["n_nodes"],
                                 "seed": seed})
            return sc.replace(name=name, description=cfg["description"])

        if name not in scenario_names():
            register(scenario)
        bad = scenario_mismatches(scenario(), cfg)
        if bad:
            raise ValueError("the program's scenario is not the "
                             "configuration: " + "; ".join(bad))
        self.step(-1)

    def step(self, k: int) -> dict:
        from repro.api.sweep import run_sweep
        spec = self.spec(k)
        art = run_sweep(spec)
        self.last = (spec.seed, art)
        values = [s[m] for s in art["samples"] for m in METRICS]
        ok = (art["engine"] == "jax-scan"
              and art["n_samples"] == spec.samples
              and all(math.isfinite(v) for v in values))
        work = spec.samples * self.cfg["n_nodes"] * art["iterations"]
        return {"work": work, "failed": not ok}

    def verify(self) -> list:
        """The widest relative gap, per metric, between the last sweep of the
        window and the float64 reference, over samples drawn from the seed
        (one from each stratum) and the healthy reference row."""
        from bench.reference.fleet import FleetReference, sweep_rows

        sweep_seed, art = self.last
        picks = verify_picks(self.traffic["samples"],
                             self.traffic["verify_samples"], self.seed)
        ref = FleetReference(self.cfg).run(
            sweep_rows(self.cfg, picks, sweep_seed, self.seed),
            self.traffic["iterations"])
        prog = [art["samples"][i] for i in picks] + [art["reference"]]
        ref_tput = ref[-1]["throughput"]
        checks = []
        for m in METRICS:
            gap = max(rel_gap(p[m], r[m]) for p, r in zip(prog, ref))
            checks.append({"name": f"{m}_rel", "value": gap})
        rec = max(rel_gap(art["samples"][i]["recovery"],
                          r["throughput"] / ref_tput)
                  for i, r in zip(picks, ref))
        checks.append({"name": "recovery_rel", "value": rec})
        return checks
