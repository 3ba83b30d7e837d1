"""Pallas TPU flash attention (forward): online-softmax over K blocks.

Grid: (batch*heads, q_blocks, k_blocks) with the k dimension 'arbitrary'
(sequential) — running max / normalizer / output accumulator live in VMEM
scratch across k steps.  BlockSpecs tile Q/K/V as (1, block, D) VMEM slabs;
block sizes default to MXU-aligned 128/512.  Causal + sliding-window masks
are generated from block indices (no mask tensor in HBM); an optional
explicit 2-D mask is streamed in (block_q, block_k) tiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale, causal, window, block_q, block_k, n_k):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # (block_q, D)
    k = k_ref[0]                                   # (block_k, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= cols <= rows
    if window:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _final():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _fa_kernel_masked(q_ref, k_ref, v_ref, mask_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, scale, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask_ref[...], s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _final():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _pad_to(x, axis, m):
    r = x.shape[axis] % m
    if r == 0:
        return x, 0
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - r)
    return jnp.pad(x, pad), m - r


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_fwd(q, k, v, mask=None, *, causal: bool = True,
                        window: int = 0, block_q: int = 128,
                        block_k: int = 512, interpret: bool):
    """q: (BH, Sq, D); k/v: (BH, Sk, D); mask: optional (Sq, Sk) bool."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, max(8, 1 << (Sq - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (Sk - 1).bit_length()))
    q, padq = _pad_to(q, 1, block_q)
    k, padk = _pad_to(k, 1, block_k)
    v, _ = _pad_to(v, 1, block_k)
    Sqp, Skp = q.shape[1], k.shape[1]
    n_q, n_k = Sqp // block_q, Skp // block_k
    scale = D ** -0.5

    scratch = [pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, D), jnp.float32)]
    grid = (BH, n_q, n_k)
    qspec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    ospec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    if mask is not None:
        mask = jnp.pad(mask, ((0, Sqp - mask.shape[0]),
                              (0, Skp - mask.shape[1])))
        mspec = pl.BlockSpec((block_q, block_k), lambda b, i, j: (i, j))
        kern = functools.partial(_fa_kernel_masked, scale=scale, n_k=n_k)
        out = pl.pallas_call(
            kern, grid=grid, in_specs=[qspec, kspec, kspec, mspec],
            out_specs=ospec, scratch_shapes=scratch,
            out_shape=jax.ShapeDtypeStruct((BH, Sqp, D), q.dtype),
            compiler_params=params, interpret=interpret,
        )(q, k, v, mask)
    else:
        # padded K rows must be masked out: extend window/causal masks
        kern = functools.partial(
            _fa_kernel, scale=scale,
            causal=causal or padk > 0, window=window, block_q=block_q,
            block_k=block_k, n_k=n_k)
        if not causal and padk > 0:
            # bidirectional with padding: use explicit mask path
            m = jnp.ones((Sq, Sk), bool)
            return flash_attention_fwd(
                q[:, :Sq], k[:, :Sk], v[:, :Sk], m, causal=False,
                window=0, block_q=block_q, block_k=block_k,
                interpret=interpret)
        out = pl.pallas_call(
            kern, grid=grid, in_specs=[qspec, kspec, kspec],
            out_specs=ospec, scratch_shapes=scratch,
            out_shape=jax.ShapeDtypeStruct((BH, Sqp, D), q.dtype),
            compiler_params=params, interpret=interpret,
        )(q, k, v)
    return out[:, :Sq]
