#!/usr/bin/env python3
"""Readings of a cell's controls at the cell's own size, on the chip.

    python3 bench/tests/control.py --workload <cell> --seeds 11 12 13

For each seed it prints, as one JSON line, what the cell's comparison reads
when the plain reference, computed one precision below the configuration's
(bfloat16 for a float32 fleet, float8 for a bfloat16 model), stands in the
program's place: the upper readings the limits are set under.  For a train
cell ``--faults`` reads the planted faults instead: half of each batch left
out, the loss taken over the rest; and on more than one chip, the FSDP
gradient exchange left out.  The benchmark's own runs never run this; the
CPU tests in ``test_controls.py`` run the same controls at a tiny size.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.common import BENCH, load_json, load_module, require_tpu  # noqa


def sweep_control(cfg, traffic, seed) -> dict:
    """bfloat16 reference against the float64 reference, on the rows a run
    of this seed compares."""
    from bench.reference.fleet import FleetReference, sweep_rows
    drv = load_module(BENCH / "drivers" / "sweep.py", "sweep_driver")
    picks = drv.verify_picks(traffic["samples"], traffic["verify_samples"],
                             seed)
    rows = sweep_rows(cfg, picks, seed * 1000 + 1, seed)
    ref = FleetReference(cfg, "float64").run(rows, traffic["iterations"])
    ctl = FleetReference(cfg, "bfloat16").run(rows, traffic["iterations"])
    out = {f"{m}_rel": max(drv.rel_gap(c[m], r[m]) for c, r in zip(ctl, ref))
           for m in drv.METRICS}
    out["recovery_rel"] = max(
        drv.rel_gap(c["throughput"] / ctl[-1]["throughput"],
                    r["throughput"] / ref[-1]["throughput"])
        for c, r in zip(ctl[:-1], ref[:-1]))
    return out


def half_batch():
    """Plant a fault: the model's loss sees half of each batch, its mean
    taken over the rest: half of the rows, or of a single row's positions
    (the first half, which causal attention lets stand alone)."""
    from repro.models import transformer
    orig = transformer.DecoderOnlyLM.loss

    def loss(self, params, batch):
        B, S = batch["tokens"].shape
        cut = ((lambda v: v[:B // 2]) if B > 1
               else (lambda v: v[:, :S // 2]))
        return orig(self, params, {k: cut(v) for k, v in batch.items()})

    transformer.DecoderOnlyLM.loss = loss
    return lambda: setattr(transformer.DecoderOnlyLM, "loss", orig)


def no_exchange():
    """Plant a fault: the FSDP exchange of gradients left out.  Each chip
    keeps its own slice of the gradient of its own rows of the batch where
    the reduce-scatter over the data axis would give it the slice of the
    sum over every chip's rows.  The model's loss is taken over each chip's
    rows apart, and the gradient of chip i's part reaches only the slice
    of each parameter that chip i holds (of a parameter held whole on every
    chip, the first chip's)."""
    import jax
    import jax.numpy as jnp
    from repro.train import train_loop
    orig = train_loop.build_train_step

    def held_by(x, sharding, i, n):
        """``x`` with its gradient let through on chip i's slice alone."""
        dims = [d for d, ax in enumerate(sharding.spec)
                if ax == "data" or (isinstance(ax, tuple) and "data" in ax)]
        if not dims:
            return x if i == 0 else jax.lax.stop_gradient(x)
        d = dims[0]
        size = x.shape[d] // n
        pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, d)
        mine = (pos >= i * size) & (pos < (i + 1) * size)
        return jnp.where(mine, x, jax.lax.stop_gradient(x))

    def build(model, train_cfg, rules, parallel):
        n = int(rules.mesh.shape["data"])
        shardings = rules.param_shardings(model.param_specs())
        loss = type(model).loss

        def local(params, batch):
            rows = batch["tokens"].shape[0] // n
            parts = [loss(model, jax.tree_util.tree_map(
                         lambda x, s: held_by(x, s, i, n), params, shardings),
                         {k: v[i * rows:(i + 1) * rows]
                          for k, v in batch.items()})
                     for i in range(n)]
            return jax.tree_util.tree_map(lambda *xs: sum(xs) / n, *parts)

        model.loss = local
        return orig(model, train_cfg, rules, parallel)

    train_loop.build_train_step = build
    return lambda: setattr(train_loop, "build_train_step", orig)


def train_readings(cfg, traffic, seed, devices, fault=None) -> dict:
    drv = load_module(BENCH / "drivers" / "train.py", "train_driver")
    undo = fault() if fault else None
    try:
        d = drv.Driver(cfg, traffic, seed, devices)
        d.setup()
    finally:
        if undo:
            undo()
    prog = d.readings
    d.release()
    ref = d.reference("float32")
    out = {"program": {c["name"]: c["value"] for c in drv.compare(prog, ref)}}
    if fault is None:
        ctl = d.reference("float8")
        out["control"] = {c["name"]: c["value"]
                          for c in drv.compare(ctl, ref)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", action="store_true",
                    help="train cells: read the planted faults")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    devices = require_tpu(int(cell["chips"]))
    for seed in args.seeds:
        t0 = time.time()
        if cfg["driver"] == "sweep":
            out = {"control": sweep_control(cfg, traffic, seed)}
        elif args.faults:
            faults = [half_batch] + ([no_exchange] if len(devices) > 1 else [])
            for fault in faults:
                t0 = time.time()
                out = train_readings(cfg, traffic, seed, devices, fault)
                out.update(fault=fault.__name__, seed=seed,
                           seconds=time.time() - t0)
                print(json.dumps(out), flush=True)
            continue
        else:
            out = train_readings(cfg, traffic, seed, devices)
        out.update(seed=seed, seconds=time.time() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
