"""The comparison that decides ``correct`` fails its controls and its
planted faults, at a tiny size on the CPU, against each cell's own limits.

The harness's look for a chip is skipped (``run_cell`` is handed the CPU);
the rest of a run is driven with the timed path broken underneath.  The
same controls at the cells' own sizes run through ``bench/tests/control.py``.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.common import BENCH, ROOT, load_json, load_module
from bench.tests.tiny import sweep_case, train_case, train_check_case

SWEEP_CELLS = ["sweep.dp256.mc64", "sweep.dp2.mc1024"]
TRAIN_CELLS = ["train.qwen3-4b.2l"]


def limits(cell):
    return load_json(BENCH / "cells" / f"{cell}.json")["limits"]


def control():
    return load_module(BENCH / "tests" / "control.py", "bench_control")


def run(cell, cfg, traffic, seed=11, devices=1):
    import bench.run as harness
    b = load_json(ROOT / "BENCHMARK.json")
    entry = harness.cell_entry(b, cell)
    return harness.run_cell(cell, entry, cfg, traffic, limits(cell), seed,
                            0.2, False, jax.devices()[:devices], b)


def verdict(readings: dict, cell: str) -> bool:
    lim = limits(cell)
    return all(readings[k] <= lim[k] for k in readings)


# --------------------------------------------------------------------- sweep
@pytest.mark.parametrize("cell", SWEEP_CELLS)
def test_sweep_program_is_correct(cell):
    cfg, traffic = sweep_case()
    assert run(cell, cfg, traffic)["correct"]


@pytest.mark.parametrize("cell", SWEEP_CELLS)
def test_sweep_bfloat16_control_is_not_correct(cell):
    cfg, traffic = sweep_case(iterations=40)
    readings = control().sweep_control(cfg, traffic, seed=5)
    assert not verdict(readings, cell), readings


def _altered(out):
    out = dict(out)
    out["t_fleet"] = out["t_fleet"] * (1 + 1e-3)     # answers 0.1% off
    return out


def _half_batch(out):
    """The first half of the samples computed and copied over the second
    half; the healthy reference row, the batch's last, left right."""
    out = dict(out)
    for k, v in out.items():
        n = v.shape[0] - 1
        out[k] = np.concatenate([v[:n // 2], v[:n - n // 2], v[n:]])
    return out


@pytest.mark.parametrize("cell", SWEEP_CELLS)
@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("fault", [_altered, _half_batch],
                         ids=["answer_altered", "half_batch"])
def test_sweep_fault_is_not_correct(cell, fault, seed, monkeypatch):
    from repro.core import jax_engine
    orig = jax_engine.run_fleet_scan

    def broken(spec, arrays):
        return fault(orig(spec, arrays))

    monkeypatch.setattr(jax_engine, "run_fleet_scan", broken)
    cfg, traffic = sweep_case(samples=8, verify_samples=4)
    assert not run(cell, cfg, traffic, seed)["correct"]


@pytest.mark.parametrize("traffic", ["mc64_it120", "mc1024_it120"])
def test_sweep_picks_cover_every_stratum(traffic):
    """At the cells' own sizes, every seed compares one sample in each
    stratum, so a fault over half of the samples is always compared."""
    drv = load_module(BENCH / "drivers" / "sweep.py", "bench_driver_sweep")
    t = load_json(BENCH / "traffic" / f"{traffic}.json")
    n, k = t["samples"], t["verify_samples"]
    assert k >= 4
    for seed in [0, 1, 2 ** 31 + 11] + list(range(1000, 1200)):
        picks = drv.verify_picks(n, k, seed)
        assert [p * k // n for p in picks] == list(range(k))


# --------------------------------------------------------------------- train
@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_program_is_correct(cell):
    cfg, traffic = train_check_case()
    out = run(cell, cfg, traffic)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_train_float8_control_is_not_correct(cell, seed):
    cfg, traffic = train_check_case()
    readings = control().train_readings(cfg, traffic, seed,
                                        jax.devices()[:1])["control"]
    assert not verdict(readings, cell), readings


def _state_unchanged():
    """Each step returns the state it was given (its loss still computed)."""
    from repro.train import train_loop
    orig = train_loop.build_train_step

    def build(*args):
        step, shardings = orig(*args)

        def stuck(state, batch):
            kept = jax.tree_util.tree_map(jnp.copy, state)
            return kept, step(state, batch)[1]
        return stuck, shardings

    train_loop.build_train_step = build
    return lambda: setattr(train_loop, "build_train_step", orig)


def _loss_altered():
    """Each step's loss 0.1% off where the step returns it."""
    from repro.train import train_loop
    orig = train_loop.build_train_step

    def build(*args):
        step, shardings = orig(*args)

        def altered(state, batch):
            state, metrics = step(state, batch)
            return state, dict(metrics, loss=metrics["loss"] * (1 + 1e-3))
        return altered, shardings

    train_loop.build_train_step = build
    return lambda: setattr(train_loop, "build_train_step", orig)


def _half_batch_train():
    return control().half_batch()


@pytest.mark.parametrize("cell", TRAIN_CELLS)
@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("fault",
                         [_half_batch_train, _state_unchanged, _loss_altered],
                         ids=["half_batch", "state_unchanged", "loss_altered"])
def test_train_fault_is_not_correct(cell, fault, seed):
    cfg, traffic = train_case()
    undo = fault()
    try:
        out = run(cell, cfg, traffic, seed)
    finally:
        undo()
    assert not out["correct"], out["checks"]


def four_device_runs(seeds) -> dict:
    """Runs of the train driver at a tiny size over a (4, 1) FSDP mesh of
    four CPU devices, held to the train cell's limits: the program on the
    first seed, and the program with the FSDP gradient exchange left out on
    every seed.  Called in a process of its own, whose CPU backend has four
    devices."""
    cell = TRAIN_CELLS[0]
    cfg, traffic = train_case(batch=8)
    traffic["mesh"] = [4, 1]
    out = {"program": run(cell, cfg, traffic, seeds[0], devices=4)}
    for seed in seeds:
        undo = control().no_exchange()
        try:
            out[f"no_exchange.{seed}"] = run(cell, cfg, traffic, seed,
                                             devices=4)
        finally:
            undo()
    return {k: {"correct": v["correct"], "checks": v["checks"]}
            for k, v in out.items()}


@pytest.fixture(scope="module")
def four_devices():
    code = ("import json, sys; sys.path[:0] = {paths!r}; "
            "from bench.tests.test_controls import four_device_runs; "
            "print(json.dumps(four_device_runs([11, 12, 13])))").format(
                paths=[str(ROOT), str(ROOT / "src")])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_train_program_is_correct_on_four_devices(four_devices):
    assert four_devices["program"]["correct"], four_devices["program"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_train_missing_exchange_is_not_correct(four_devices, seed):
    out = four_devices[f"no_exchange.{seed}"]
    assert not out["correct"], out["checks"]
