"""Pallas TPU chunked WKV6 kernel.

Grid: (B*H, n_chunks) with the chunk dimension 'arbitrary' (sequential);
the (D, D) fp32 recurrent state lives in VMEM scratch across chunks.  Each
step processes an (L, D) tile of r/k/v/log-decay: the intra-chunk pairwise
decay matrix is built from cumulative log-decays (all exponents <= 0 —
numerically safe), the inter-chunk part is one (L,D)x(D,D) matmul against
the carried state.  This is the TPU-native adaptation of the GPU recurrence:
sequential over chunks to keep the state resident, parallel over B*H.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref, s_scr,
                *, L, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)               # (L, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)               # log decay, < 0
    u = u_ref[0].astype(jnp.float32)               # (1, 1, D) block -> (1, D)

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # inclusive prefix sum as a triangular matmul (Mosaic has no cumsum)
    cw = jax.lax.dot((row >= col).astype(jnp.float32), w,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    cwx = cw - w                                   # exclusive
    S_prev = s_scr[...]

    # inter-chunk: y_i += (r_i * exp(cwx_i)) @ S_prev
    y = jax.lax.dot(r * jnp.exp(cwx), S_prev,
                    preferred_element_type=jnp.float32)

    # intra-chunk: A_ij = sum_d r_i k_j exp(cwx_i - cw_j), strictly lower
    expo = cwx[:, None, :] - cw[None, :, :]        # (L, L, D)
    tri = (jax.lax.broadcasted_iota(jnp.int32, expo.shape, 0)
           > jax.lax.broadcasted_iota(jnp.int32, expo.shape, 1))
    pair = jnp.where(tri, jnp.exp(jnp.minimum(expo, 0.0)), 0.0)
    A = jnp.sum(r[:, None, :] * k[None, :, :] * pair, axis=-1)
    diag = jnp.sum(r * u * k, axis=-1)             # u-weighted current token
    y = y + jax.lax.dot(A, v, preferred_element_type=jnp.float32) \
        + diag[:, None] * v

    # state update: S = diag(exp(cw_L)) S + sum_j (k_j exp(cw_L - cw_j))^T v_j
    cw_last = cw[L - 1:L]                          # (1, D), static slice
    k_scaled = k * jnp.exp(cw_last - cw)
    s_scr[...] = S_prev * jnp.exp(cw_last).T + jax.lax.dot(
        k_scaled.T, v, preferred_element_type=jnp.float32)

    o_ref[0] = y.astype(o_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        s_out_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_fwd(r, k, v, w_log, u, *, chunk: int = 64, interpret: bool):
    """r,k,v,w_log: (B,S,H,D); u: (H,D) -> (y (B,S,H,D), state (B,H,D,D))."""
    B, S, H, D = r.shape
    L = min(chunk, S)
    pad = (-S) % L
    n = (S + pad) // L

    def prep(x):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x

    rf, kf, vf = prep(r), prep(k), prep(v)
    # padded steps: w_log = 0 (identity decay), k = 0 (no contribution)
    wf = prep(w_log)
    if pad:
        valid = (jnp.arange(S + pad) < S)[None, :, None]
        wf = jnp.where(valid, wf, 0.0)
        kf = jnp.where(valid, kf, 0.0)
    # u per (b,h) row: layout must match prep()'s (B*H) ordering.  The
    # singleton middle axis makes u's block (1, 1, D) equal the array in its
    # last two dims, as the TPU's (8, 128) tiling requires of a block.
    uf = jnp.broadcast_to(u[None], (B, H, D)).reshape(B * H, 1, D)

    spec_t = pl.BlockSpec((1, L, D), lambda b, c: (b, c, 0))
    out, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, L=L, n_chunks=n),
        grid=(B * H, n),
        in_specs=[spec_t, spec_t, spec_t, spec_t,
                  pl.BlockSpec((1, 1, D), lambda b, c: (b, 0, 0))],
        out_specs=[spec_t,
                   pl.BlockSpec((1, D, D), lambda b, c: (b, 0, 0))],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((B * H, S + pad, D), r.dtype),
                   jax.ShapeDtypeStruct((B * H, D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    y = out[:, :S].reshape(B, H, S, D).transpose(0, 2, 1, 3)
    return y, s_out.reshape(B, H, D, D)
