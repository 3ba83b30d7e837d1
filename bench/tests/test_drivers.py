"""Each driver's set-up, window step and comparison at a tiny size on the
CPU, called directly (the command itself refuses to run off the chip)."""
import jax
import pytest

from bench.common import load_module, BENCH
from bench.tests.tiny import sweep_case, train_case


def driver(name):
    return load_module(BENCH / "drivers" / f"{name}.py", f"bench_driver_{name}")


def test_sweep_driver_window_and_reference_agree():
    cfg, traffic = sweep_case()
    d = driver("sweep").Driver(cfg, traffic, 2 ** 31 + 7, jax.devices()[:1])
    d.setup()
    rec = [d.step(k) for k in range(2)]
    assert all(not r["failed"] and r["work"] == 3 * cfg["n_nodes"] * 12
               for r in rec)
    checks = d.verify()
    assert {c["name"] for c in checks} == {
        "t_fleet_s_rel", "throughput_rel", "lead_max_s_rel",
        "fleet_power_w_rel", "recovery_rel"}
    assert max(c["value"] for c in checks) < 1e-9


def test_train_driver_window_and_reference_agree():
    cfg, traffic = train_case()
    d = driver("train").Driver(cfg, traffic, 2 ** 31 + 7, jax.devices()[:1])
    d.setup()
    rec = [d.step(k) for k in range(2)]
    assert all(not r["failed"] and r["tokens"] == 32 for r in rec)
    checks = {c["name"]: c["value"] for c in d.verify()}
    assert set(checks) == {"loss_rel", "grad1_leaf_rel", "delta3_leaf_rel"}
    assert checks["loss_rel"] < 1e-2
