"""Pallas TPU grouped GEMM: the padded per-expert contraction of the MoE
block ((E,C,d) x (E,d,h) -> (E,C,h)) — the paper's §VII-C platform pads
expert GEMMs for balanced computation, which maps exactly to this kernel.

Grid: (E, C/bc, h/bh, d/bd); the contraction (d) dimension is 'arbitrary'
(sequential) with an fp32 VMEM accumulator; (bc, bd) x (bd, bh) tiles are
MXU-aligned 128-multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, w_ref, o_ref, acc, *, n_d):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _final():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _pad_dim(x, axis, m):
    r = (-x.shape[axis]) % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("block_c", "block_h", "block_d",
                                              "interpret"))
def moe_gemm_fwd(x, w, *, block_c: int = 128, block_h: int = 128,
                 block_d: int = 512, interpret: bool):
    """x: (E, C, d), w: (E, d, h) -> (E, C, h)."""
    E, C, d = x.shape
    h = w.shape[2]
    block_c = min(block_c, max(8, 1 << (C - 1).bit_length()))
    block_h = min(block_h, max(8, 1 << (h - 1).bit_length()))
    block_d = min(block_d, max(8, 1 << (d - 1).bit_length()))
    xp = _pad_dim(_pad_dim(x, 1, block_c), 2, block_d)
    wp = _pad_dim(_pad_dim(w, 1, block_d), 2, block_h)
    Cp, dp, hp = xp.shape[1], xp.shape[2], wp.shape[2]
    n_c, n_h, n_d = Cp // block_c, hp // block_h, dp // block_d

    out = pl.pallas_call(
        functools.partial(_mm_kernel, n_d=n_d),
        grid=(E, n_c, n_h, n_d),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((1, block_d, block_h),
                         lambda e, i, j, kk: (e, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_h),
                               lambda e, i, j, kk: (e, i, j)),
        scratch_shapes=[pltpu.VMEM((block_c, block_h), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((E, Cp, hp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(xp, wp)
    return out[:, :C, :h]
