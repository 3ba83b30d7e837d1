"""The four Pallas kernels compile for a TPU v5e at real widths.

Interpret mode on the CPU cannot show what the chip's compiler refuses
(block shapes off the (8, 128) tiling, too much VMEM), so each kernel is
compiled here for a described v5e chip, with ``interpret=False``, and the
program must hold the Mosaic kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.moe_gemm.kernel import moe_gemm_fwd
from repro.kernels.rmsnorm.kernel import rmsnorm_fwd
from repro.kernels.rwkv6_wkv.kernel import wkv6_fwd


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (a TPU entry written here could not be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes, **static):
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=sharding)
            for s in shapes]
    return fn.lower(*args, interpret=False, **static).compile().as_text()


# qwen3-4b attention: 32 heads x 4096 tokens x head dim 128, bf16
@pytest.mark.parametrize("kind", ["causal", "window", "mask"])
def test_flash_attention_compiles_for_v5e(one_chip, kind):
    qkv = ((32, 4096, 128), jnp.bfloat16)
    mask = ((4096, 4096), jnp.bool_) if kind == "mask" else None
    static = {"causal": kind != "mask", "window": 1024 if kind == "window"
              else 0}
    text = _compiled_text(flash_attention_fwd, one_chip, qkv, qkv, qkv, mask,
                          **static)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_compiles_for_v5e(one_chip, residual):
    x = ((8192, 2560), jnp.bfloat16)
    w = ((2560,), jnp.float32)
    text = _compiled_text(rmsnorm_fwd, one_chip, x, w,
                          x if residual else None)
    assert "tpu_custom_call" in text


def test_moe_gemm_compiles_for_v5e(one_chip):
    # deepseek-v3-16b's expert width: 8 experts x 1024 slots, 2048 -> 1408
    text = _compiled_text(moe_gemm_fwd, one_chip,
                          ((8, 1024, 2048), jnp.bfloat16),
                          ((8, 2048, 1408), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_wkv6_compiles_for_v5e(one_chip):
    # rwkv6-3b: 40 heads of 64, one 4096-token sequence
    t = ((1, 4096, 40, 64), jnp.bfloat16)
    text = _compiled_text(wkv6_fwd, one_chip, t, t, t, t,
                          ((40, 64), jnp.float32))
    assert "tpu_custom_call" in text
