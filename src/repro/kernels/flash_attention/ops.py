"""jit'd model-facing wrapper: GQA layout handling around the Pallas kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q, k, v, mask=None, *, causal=None, window: int = 0):
    """q: (B, Sq, H, D), k/v: (B, Sk, kvH, D) -> (B, Sq, H, D).

    mask: None or broadcastable bool whose last two dims are (Sq, Sk).
    Two cases run the jnp oracle instead of the kernel, on every backend:
    Sq == 1 (decode), and masks that differ per batch or head.  The
    trainer reaches neither: it uses the XLA ``chunked`` attention
    (``repro.models.attention``), not this wrapper.
    """
    B, Sq, H, D = q.shape
    Sk, kvH = k.shape[1], k.shape[2]
    if kvH != H:
        rep = H // kvH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)

    mask2d = None
    if mask is not None:
        m = jnp.asarray(mask)
        m = jnp.broadcast_to(m, m.shape[:-2] + (Sq, Sk))
        if m.ndim > 2 and all(s == 1 for s in m.shape[:-2]):
            m = m.reshape(Sq, Sk)
        if m.ndim == 2:
            mask2d = m
        else:                                  # per-batch/head masks: oracle
            out = flash_attention_ref(qf, kf, vf, causal=False,
                                      mask=m.reshape(-1, Sq, Sk))
            return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)

    if Sq == 1:
        out = flash_attention_ref(qf, kf, vf, causal=False, mask=mask2d)
    else:
        out = flash_attention_fwd(
            qf, kf, vf, mask2d,
            causal=bool(causal) if causal is not None else False,
            window=window, interpret=interpret_mode())
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
