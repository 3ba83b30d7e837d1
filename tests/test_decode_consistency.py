"""prefill + decode_step must agree with the full forward pass (teacher
forcing) for every model family."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced_config
from repro.models.attention import set_attention_impl
from repro.models import build_model, make_batch
from repro.models.common import init_params

FAMS = ["qwen3-4b", "deepseek-7b", "hymba-1.5b", "rwkv6-3b",
        "whisper-medium", "llama-3.2-vision-90b", "mistral-7b",
        "grok-1-314b", "deepseek-moe-16b", "nemotron-4-15b",
        "deepseek-v2-lite"]


def _nodrop(cfg):
    if cfg.moe is not None and cfg.moe.capacity_factor is not None:
        # capacity token-dropping is seq-length dependent; disable for the
        # exactness check (the dropping path is tested in test_moe.py)
        return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                   capacity_factor=8.0))
    return cfg


@pytest.fixture(autouse=True)
def _same_attention_path():
    """forward uses the chunked path, decode the naive one; pin both to
    'xla' so this test checks cache algebra, not softmax summation order
    (chunked==naive equivalence is covered in test_kernels.py)."""
    set_attention_impl("xla")
    yield
    set_attention_impl("chunked")


@pytest.mark.slow
@pytest.mark.parametrize("arch", FAMS)
def test_prefill_decode_matches_forward(arch):
    cfg = _nodrop(get_reduced_config(arch))
    model = build_model(cfg, max_cache_len=24)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 16)
    logits_full, _ = jax.jit(model.forward)(params, batch)
    pre = {k: (v[:, :12] if k in ("tokens", "labels") else v)
           for k, v in batch.items()}
    lg, cache = jax.jit(model.prefill)(params, pre)
    errs = [float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, 11])))]
    step = jax.jit(model.decode_step)
    for t in range(12, 15):
        lg, cache = step(params, batch["tokens"][:, t:t + 1], cache)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits_full[:, t]))))
    assert max(errs) < 2e-2, errs


def test_sliding_window_ring_cache():
    """mistral-style ring buffer: decode far past the window stays finite
    and ignores evicted positions."""
    cfg = get_reduced_config("mistral-7b").replace(window=8)
    model = build_model(cfg, max_cache_len=48)       # > window -> ring
    assert model.cache_window == 8
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    batch = make_batch(cfg, 1, 32)
    lg, cache = jax.jit(model.prefill)(params, batch)
    step = jax.jit(model.decode_step)
    for t in range(8):
        lg, cache = step(params, jnp.full((1, 1), 7, jnp.int32), cache)
        assert bool(jnp.isfinite(lg).all())
    assert int(cache["pos"]) == 40
