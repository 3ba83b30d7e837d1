"""Multi-device semantics via subprocesses (8 host devices).

conftest must NOT set XLA_FLAGS (smoke tests see 1 device), so each test
spawns a fresh interpreter with the flag and runs a self-contained script.
"""
import os
import subprocess
import sys
import textwrap

import pytest

# every case spawns a fresh interpreter and compiles jax programs
pytestmark = pytest.mark.slow

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_ring_all_gather_matches_allgather():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.parallel.collectives import ring_all_gather
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
        f = jax.shard_map(lambda s: ring_all_gather(s, "data"),
                          mesh=mesh, in_specs=P("data", None),
                          out_specs=P("data", None, None), check_vma=False)
        out = f(x)   # (8*8//8? -> (8, 1, 4) stacked chunks per shard
        out = np.asarray(out).reshape(8, 8, 1, 4)
        for r in range(8):
            np.testing.assert_allclose(out[r].reshape(8, 4), np.asarray(x))
        print("ring ok")
    """))


def test_compressed_psum_close_to_exact():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.parallel.compression import compressed_psum
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        f = jax.shard_map(lambda s: compressed_psum(s, "data"), mesh=mesh,
                          in_specs=P("data", None), out_specs=P("data", None),
                          check_vma=False)
        approx = np.asarray(f(x))[0]
        exact = np.asarray(x.sum(0))
        scale = np.abs(np.asarray(x)).max() / 127.0
        assert np.abs(approx - exact).max() <= 8 * scale * 0.5 + 1e-6
        print("psum ok")
    """))


def test_sharded_train_matches_single_device():
    """2x4 mesh FSDP+TP step produces the same loss as 1-device."""
    code_t = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced_config, TrainConfig, ParallelConfig
        from repro.models import build_model, make_batch
        from repro.parallel.fsdp import build_train_step, init_train_state
        from repro.parallel.sharding import ShardingRules
        import numpy as onp
        cfg = get_reduced_config("llama3.1-8b").replace(
            d_model=64, n_heads=4, n_kv_heads=4, d_head=16, n_layers=2,
            vocab_size=512, d_ff=128)
        mesh = jax.sharding.Mesh(
            onp.array(jax.devices()).reshape(%s), ("data", "model"))
        parallel = ParallelConfig()
        model = build_model(cfg, max_cache_len=32)
        rules = ShardingRules(mesh, cfg, parallel)
        step, _ = build_train_step(model, TrainConfig(warmup_steps=1),
                                   rules, parallel)
        with mesh:
            state = init_train_state(model, rules, parallel, seed=3)
            batch = make_batch(cfg, 8, 16)
            for _ in range(3):
                state, m = step(state, batch)
        print("LOSS=%%.6f" %% float(m["loss"]))
    """
    o1 = run_py(code_t % "(2, 4)", devices=8)
    o2 = run_py(code_t % "(1, 1)", devices=1)
    l1 = float(o1.split("LOSS=")[1])
    l2 = float(o2.split("LOSS=")[1])
    assert abs(l1 - l2) < 5e-2, (l1, l2)


def test_fsdp_prefetch_chain():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.parallel.collectives import make_fsdp_prefetch_fn
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        f = jax.jit(make_fsdp_prefetch_fn(mesh))
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 32, 32)) * 0.1
        out = f(x, w.reshape(3, 8, 4, 32).transpose(0, 1, 2, 3).reshape(3, 32, 32))
        # reference: plain chain
        ref = x
        for i in range(3):
            ref = jax.nn.relu(ref @ w[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)
        print("prefetch ok")
    """))


def test_moe_shard_map_matches_scatter():
    """grok-style TP experts: forced-local shard_map dispatch == pjit scatter
    (big capacity -> no drops; tolerance = bf16 partial-sum reordering)."""
    print(run_py("""
        import jax, numpy as np, jax.numpy as jnp, dataclasses
        from repro.configs import get_reduced_config, ParallelConfig
        from repro.models import build_model, make_batch
        from repro.models.common import init_params
        from repro.parallel.act import activation_sharding
        from repro.parallel.sharding import ShardingRules
        from repro.parallel.moe_shard_map import set_moe_dispatch
        mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(2, 8),
                                 ("data", "model"))
        cfg = get_reduced_config("grok-1-314b").replace(d_ff=64)
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, d_expert=64, capacity_factor=8.0))
        model = build_model(cfg)
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
        batch = make_batch(cfg, 4, 16)
        rules = ShardingRules(mesh, cfg, ParallelConfig())
        with mesh:
            with activation_sharding(mesh, rules.activation_rules()):
                l1, _ = jax.jit(model.loss)(params, batch)
                set_moe_dispatch("shard_map")
                l2, _ = jax.jit(model.loss)(params, batch)
        d = abs(float(l1 - l2))
        assert d < 2e-2, (float(l1), float(l2))
        print("moe shard_map ok", d)
    """, devices=16))
