#!/usr/bin/env python3
"""Readings of a latent-attention expert cell's controls at the cell's own
size, on the chip.

    python3 bench/tests/control_mla_moe.py --workload <cell> --seeds 11 12 13
    python3 bench/tests/control_mla_moe.py --workload <cell> --seeds 11 --faults

As ``control.py`` does for the train cells, through
``bench/drivers/train_mla_moe.py``: for each seed, one JSON line with what
the cell's comparison reads for the program and for the float8 control
(the reference with every matrix product's operands rounded to float8);
with ``--faults``, for the program with half of each batch left out.  Each
side is judged against the cell's own limits (``bench/cells/<cell>.json``)
as ``bench/run.py`` judges a run: ``correct`` per side, and the numbers
over their limit.  Each line also counts, over the expert layers, the top-k assignments of the
seed's first batch that the program, from the seed's weights, routes to
another expert than the reference does: routing near-ties are the
comparison's one discontinuity.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.common import BENCH, load_json, load_module, require_tpu  # noqa
from bench.drivers.train import compare, flat                       # noqa
from bench.tests.control import half_batch                          # noqa


def program_routing(model, params, tokens) -> list:
    """Each expert layer's top-k indices (T, k) in the program's own
    forward pass: its blocks layer by layer, routing as ``moe_forward``
    does."""
    import jax
    import jax.numpy as jnp
    from repro.models.common import apply_norm, tree_get
    cfg = model.cfg
    x = model._embed(params, tokens)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    out = []
    for gi, (n, dense) in enumerate(model.layer_groups()):
        for i in range(n):
            lp = tree_get(params[f"g{gi}"], i)
            x = x + model._mixer(lp, x, positions, 0, dense, None)
            if not dense:
                h = apply_norm(cfg, lp["ln2"], x).reshape(-1, cfg.d_model)
                probs = jax.nn.softmax(h.astype(jnp.float32)
                                       @ lp["ffn"]["router"], -1)
                out.append(jax.lax.top_k(probs, cfg.moe.top_k)[1])
            x = x + model._ffn(lp, x, dense)[0]
    return out


def routing_departures(drv, d) -> list:
    """Per expert layer, the assignments of the first checked batch that
    the program and the reference route differently, from the seed's
    weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.reference import deepseek_v2 as ref
    from bench.reference.qwen3 import seed_key
    from repro.models import build_model

    cfg = d.cfg
    model = build_model(drv.model_config(cfg))
    tokens = jnp.asarray(d.batches[0]["tokens"])
    p = jax.jit(lambda k: ref.init_params(cfg, k))(seed_key(d.seed))
    spec = model.param_specs()
    is_spec = lambda x: hasattr(x, "axes")          # noqa: E731
    paths = list(flat(spec, is_spec))
    params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(spec, is_leaf=is_spec),
        [p[k] for k in paths])
    prog = jax.jit(lambda q: program_routing(model, q, tokens))(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q: ref.routing(cfg, q, tokens))(p)
    out = []
    for a, b in zip(prog, want):
        a = np.sort(np.asarray(a).reshape(-1, a.shape[-1]), -1)
        b = np.sort(np.asarray(b).reshape(-1, b.shape[-1]), -1)
        out.append(int(sum(len(set(x) - set(y)) for x, y in zip(a, b))))
    return out


def verdict(numbers: dict, limits: dict) -> dict:
    """What ``bench/run.py`` would make of these compared numbers: correct
    when each is finite and within its limit."""
    over = sorted(k for k, v in numbers.items()
                  if not (math.isfinite(v) and v <= limits[k]))
    return {"correct": not over, "over": over}


def readings(cfg, traffic, seed, devices, fault=None) -> dict:
    drv = load_module(BENCH / "drivers" / "train_mla_moe.py",
                      "bench_driver_train_mla_moe")
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
    out = {"program": {c["name"]: c["value"] for c in compare(prog, ref)}}
    if fault is None:
        ctl = d.reference("float8")
        out["control"] = {c["name"]: c["value"]
                          for c in compare(ctl, ref)}
        out["routing_departures"] = routing_departures(drv, d)
        out["assignments_per_layer"] = (traffic["global_batch"]
                                        * traffic["seq_len"]
                                        * cfg["num_experts_per_tok"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", action="store_true",
                    help="read the planted half-batch fault")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "cells" / f"{args.workload}.json")["limits"]
    devices = require_tpu(int(cell["chips"]))
    for seed in args.seeds:
        t0 = time.time()
        out = readings(cfg, traffic, seed, devices,
                       half_batch if args.faults else None)
        out["verdict"] = {side: verdict(out[side], limits)
                          for side in ("program", "control") if side in out}
        out.update(limits=limits, seed=seed, seconds=time.time() - t0,
                   fault="half_batch" if args.faults else None)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
