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


@pytest.mark.parametrize("block", [4, 8])
def test_reference_attention_by_blocks_is_whole_attention(block):
    """The reference's attention over blocks of queries, each with its own
    causal rows, gives the same output and gradient as one block."""
    import jax.numpy as jnp
    from bench.reference.qwen3 import _dot, attend
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 16, 4, 8))
               for i in range(3))
    dot = _dot("float32")

    def f(b):
        return lambda q: jnp.sum(jnp.sin(attend(dot, q, k, v, b)))
    whole, g_whole = jax.value_and_grad(f(16))(q)
    part, g_part = jax.value_and_grad(f(block))(q)
    assert part == pytest.approx(float(whole), rel=1e-6)
    assert jnp.max(jnp.abs(g_part - g_whole)) < 1e-5
    assert attend(dot, q, k, v, 5).shape == (2, 16, 32)      # 5 does not divide 16
