"""The comparison that decides ``correct`` fails its controls and its
planted faults, at a tiny size on the CPU, against each cell's own limits.

The harness's look for a chip is skipped (``run_cell`` is handed the CPU);
the rest of a run is driven with the timed path broken underneath.  The
same controls at the cells' own sizes run through ``bench/tests/control.py``.
"""
import jax
import numpy as np
import pytest

from bench.common import BENCH, ROOT, load_json, load_module
from bench.tests.tiny import sweep_case

SWEEP_CELLS = ["sweep.dp256.mc64", "sweep.dp2.mc1024"]


def limits(cell):
    return load_json(BENCH / "cells" / f"{cell}.json")["limits"]


def run(cell, cfg, traffic, seed=11):
    import bench.run as harness
    b = load_json(ROOT / "BENCHMARK.json")
    entry = harness.cell_entry(b, cell)
    return harness.run_cell(cell, entry, cfg, traffic, limits(cell), seed,
                            0.2, False, jax.devices()[:1], b)


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
    control = load_module(BENCH / "tests" / "control.py", "bench_control")
    cfg, traffic = sweep_case(iterations=40)
    readings = control.sweep_control(cfg, traffic, seed=5)
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
