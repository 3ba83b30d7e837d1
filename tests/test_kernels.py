"""Pallas kernel sweeps (deliverable c): shapes x dtypes vs the pure-jnp
oracle, in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.moe_gemm import moe_gemm, moe_gemm_ref
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref
from repro.kernels.rwkv6_wkv import wkv6, wkv6_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _keys(n):
    return jax.random.split(jax.random.PRNGKey(42), n)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("S", [64, 200, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kw", [dict(causal=True), dict(causal=True, window=32),
                                dict(causal=False)])
def test_flash_attention_sweep(S, dtype, kw):
    B, H, kvH, D = 2, 4, 2, 32
    ks = _keys(3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, kvH, D), dtype)
    v = jax.random.normal(ks[2], (B, S, kvH, D), dtype)
    out = flash_attention(q, k, v, causal=kw.get("causal"),
                          window=kw.get("window", 0),
                          mask=None if kw.get("causal") is not False else None)
    kk = jnp.repeat(k, H // kvH, 2)
    vv = jnp.repeat(v, H // kvH, 2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = kk.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = vv.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    ref = flash_attention_ref(qf, kf, vf, **kw).reshape(B, H, S, D)
    ref = ref.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_explicit_mask():
    B, S, H, D = 1, 96, 2, 16
    ks = _keys(3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    mask = jnp.tril(jnp.ones((S, S), bool), k=-1) | jnp.eye(S, dtype=bool)
    out = flash_attention(q, k, v, mask=mask)
    ref = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ------------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("shape", [(4, 128), (2, 100, 256), (1, 7, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    ks = _keys(3)
    x = jax.random.normal(ks[0], shape, dtype)
    w = jax.random.normal(ks[1], shape[-1:], jnp.float32)
    np.testing.assert_allclose(
        np.asarray(rmsnorm(x, w), np.float32),
        np.asarray(rmsnorm_ref(x, w), np.float32), atol=TOL[dtype])
    r = jax.random.normal(ks[2], shape, dtype)
    o1, res1 = rmsnorm(x, w, r)
    o2, res2 = rmsnorm_ref(x, w, r)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(res1, np.float32),
                               np.asarray(res2, np.float32), atol=TOL[dtype])


# ------------------------------------------------------------------ moe gemm
@pytest.mark.parametrize("ECdh", [(4, 64, 96, 200), (2, 100, 48, 64),
                                  (8, 8, 16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm_sweep(ECdh, dtype):
    E, C, d, h = ECdh
    ks = _keys(2)
    x = jax.random.normal(ks[0], (E, C, d), dtype)
    w = jax.random.normal(ks[1], (E, d, h), dtype)
    np.testing.assert_allclose(
        np.asarray(moe_gemm(x, w), np.float32),
        np.asarray(moe_gemm_ref(x, w), np.float32),
        atol=TOL[dtype] * np.sqrt(d), rtol=TOL[dtype])


# ---------------------------------------------------------------------- wkv6
@pytest.mark.parametrize("S", [16, 64, 130])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_sweep(S, dtype):
    B, H, D = 2, 3, 16
    ks = _keys(5)
    r = (jax.random.normal(ks[0], (B, S, H, D)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, H, D)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, S, H, D)) * 0.5).astype(dtype)
    wl = -jnp.exp(jax.random.normal(ks[3], (B, S, H, D))).astype(dtype)
    u = jax.random.normal(ks[4], (H, D))
    y1, s1 = wkv6(r, k, v, wl, u)
    y2, s2 = wkv6_ref(r, k, v, wl, u)
    tol = 5e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=tol)


# -------------------------------------------------- property: random shapes
@pytest.mark.slow
@settings(deadline=None, max_examples=15)
@given(S=st.integers(8, 96), D=st.sampled_from([8, 16, 32]),
       H=st.integers(1, 4))
def test_flash_attention_property(S, D, H):
    ks = _keys(3)
    q = jax.random.normal(ks[0], (1, S, H, D))
    k = jax.random.normal(ks[1], (1, S, H, D))
    v = jax.random.normal(ks[2], (1, S, H, D))
    out = flash_attention(q, k, v, causal=True)
    qf = q.transpose(0, 2, 1, 3).reshape(H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(H, S, D)
    ref = flash_attention_ref(qf, kf, vf, causal=True)
    np.testing.assert_allclose(
        np.asarray(out[0].transpose(1, 0, 2)), np.asarray(ref), atol=3e-5)


@settings(deadline=None, max_examples=15)
@given(rows=st.integers(1, 300), d=st.sampled_from([32, 128, 384]))
def test_rmsnorm_property(rows, d):
    ks = _keys(2)
    x = jax.random.normal(ks[0], (rows, d))
    w = jax.random.normal(ks[1], (d,))
    np.testing.assert_allclose(np.asarray(rmsnorm(x, w)),
                               np.asarray(rmsnorm_ref(x, w)), atol=2e-5)


# ------------------------------------------------ chunked == naive (fp32)
def _attn_grads(f, q, k, v):
    """The output and the gradients of a scalar of it w.r.t. q, k and v."""
    w = jax.random.normal(_keys(4)[3], jax.eval_shape(f, q, k, v).shape)
    out, vjp = jax.vjp(jax.jit(f), q, k, v)
    return (out,) + jax.jit(vjp)(w)


def _assert_all_close(got, want, atol):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


@pytest.mark.parametrize("S,chunk,kvH,D,Dv,scale", [
    pytest.param(64, 16, 2, 16, 16, None, id="64-16"),
    pytest.param(200, 64, 2, 16, 16, None, id="200-64"),
    pytest.param(96, 1024, 2, 16, 16, None, id="96-1024"),
    pytest.param(256, 32, 1, 16, 16, None, id="gqa4-256-32"),
    pytest.param(200, 64, 1, 16, 16, None, id="gqa4-200-64"),
    pytest.param(128, 32, 4, 24, 16, 0.3, id="mla-128-32"),
    pytest.param(200, 64, 2, 24, 16, 0.3, id="mla-gqa2-200-64"),
])
def test_sdpa_flash_matches_naive(S, chunk, kvH, D, Dv, scale):
    """Causal self-attention (the block-skipping schedule): output and
    gradients w.r.t. q, k, v equal the naive masked SDPA in float32."""
    from repro.models.attention import sdpa, sdpa_flash, make_mask
    B, H = 2, 4
    ks = _keys(3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, kvH, D))
    v = jax.random.normal(ks[2], (B, S, kvH, Dv))
    got = _attn_grads(lambda q, k, v: sdpa_flash(
        q, k, v, causal=True, chunk=chunk, scale=scale), q, k, v)
    want = _attn_grads(lambda q, k, v: sdpa(
        q, k, v, make_mask(S, S, causal=True), scale), q, k, v)
    assert got[0].shape == (B, S, H, Dv)
    _assert_all_close(got, want, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=3e-6)
    # sliding window with a traced window_eff
    we = jnp.asarray(32, jnp.int32)
    out = sdpa_flash(q, k, v, causal=True, window_eff=we, chunk=chunk,
                     scale=scale)
    ref = sdpa(q, k, v, make_mask(S, S, causal=True, window=32), scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def _scan_rows(jaxpr):
    """(length, query rows of the carry) of every scan in a jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append((eqn.params["length"],
                          eqn.outvars[0].aval.shape[-2]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scan_rows(sub)
    return found


@pytest.mark.parametrize("kind", ["traced_window", "static_window",
                                  "q_offset", "not_causal", "cross",
                                  "offset_prefill"])
def test_sdpa_flash_fallback_scans_every_chunk(kind):
    """Calls that are not causal self-attention with a static zero window
    and offset keep the scan of every KV chunk against all queries, and
    match the naive masked SDPA."""
    from repro.models.attention import flash_blocks, sdpa, sdpa_flash, make_mask
    B, H, kvH, D, chunk = 2, 4, 2, 16, 64
    Sq, Sk = {"cross": (48, 200), "offset_prefill": (72, 200)}.get(
        kind, (200, 200))
    kw = {"traced_window": dict(causal=True,
                                window_eff=jnp.asarray(32, jnp.int32)),
          "static_window": dict(causal=True, window_eff=32),
          "q_offset": dict(causal=True, q_offset=8),
          "not_causal": dict(causal=False),
          "cross": dict(causal=False),
          "offset_prefill": dict(causal=True, q_offset=Sk - Sq)}[kind]
    mask = make_mask(Sq, Sk, causal=kw["causal"],
                     window=32 if "window_eff" in kw else 0,
                     offset=kw.get("q_offset", 0))
    ks = _keys(3)
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Sk, kvH, D))
    v = jax.random.normal(ks[2], (B, Sk, kvH, D))
    n = -(-Sk // chunk)
    assert sorted(flash_blocks(Sq, Sk, chunk, **kw)) == [
        (i, j) for i in range(-(-Sq // chunk)) for j in range(n)]

    def f(q, k, v):
        return sdpa_flash(q, k, v, chunk=chunk, **kw)
    assert _scan_rows(jax.make_jaxpr(f)(q, k, v).jaxpr) == [(n, Sq)]
    got = _attn_grads(f, q, k, v)
    want = _attn_grads(lambda q, k, v: sdpa(q, k, v, mask), q, k, v)
    _assert_all_close(got, want, atol=3e-5)


@pytest.mark.parametrize("kw,skips", [
    (dict(causal=True), True),
    (dict(causal=True, window_eff=0, q_offset=0), True),
    (dict(causal=False), False),
    (dict(causal=True, window_eff=4096), False),
    (dict(causal=True, q_offset=1), False),
    (dict(causal=True, window_eff=jnp.asarray(0, jnp.int32)), False),
])
def test_flash_blocks_schedule(kw, skips):
    """At 4,096 tokens and sdpa_flash's block size, causal self-attention
    visits each (query block, key block) pair on and below the diagonal
    once, n(n+1)/2 pairs; the fallback visits the whole n x n grid."""
    import inspect

    from repro.models.attention import flash_blocks, sdpa_flash
    chunk = inspect.signature(sdpa_flash).parameters["chunk"].default
    S = 4096
    n = S // chunk
    pairs = flash_blocks(S, S, chunk, **kw)
    assert len(set(pairs)) == len(pairs)
    if skips:
        assert len(pairs) == n * (n + 1) // 2
        assert set(pairs) == {(i, j) for i in range(n) for j in range(i + 1)}
    else:
        assert set(pairs) == {(i, j) for i in range(n) for j in range(n)}
