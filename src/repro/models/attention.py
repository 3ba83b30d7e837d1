"""Attention: GQA SDPA (grouped, no materialized KV repeat), qk-norm, biases,
causal/sliding/bidirectional masks, cross-attention, multi-head latent
attention (DeepSeek-V2's MLA: keys and values from a normed latent, expanded
per head), and decode over KV caches (full-length or sliding-window ring
buffers; MLA decodes over the expanded per-head keys and values).

Default backend is plain XLA einsums (what the dry-run lowers for the 512-chip
mesh); the Pallas flash-attention kernel from ``repro.kernels`` can be swapped
in with ``set_attention_impl("pallas")`` (validated in interpret mode on CPU).
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.common import (ParamSpec, apply_rope, rmsnorm, rope_freqs,
                                 yarn_mscale)

_ATTN_IMPL = "chunked"


def set_attention_impl(impl: str) -> None:
    """'chunked' (default): XLA flash-structured online-softmax over KV
    blocks — the faithful counterpart of the paper stack's FlashAttention
    (on real TPUs the Pallas kernel takes this role: 'pallas').
    'xla': naive S² materialization (ablation baseline)."""
    global _ATTN_IMPL
    assert impl in ("xla", "pallas", "chunked", "stub"), impl
    _ATTN_IMPL = impl


def get_attention_impl() -> str:
    return _ATTN_IMPL


# --------------------------------------------------------------------------- #
# Specs
# --------------------------------------------------------------------------- #
def attn_specs(cfg, kv_src_dim: Optional[int] = None) -> Dict[str, ParamSpec]:
    """Projection specs.  kv_src_dim != None -> cross-attention (kv from there).

    Logical axes: 'embed' is the FSDP-sharded model dim, 'heads'/'kv_heads'
    the TP-sharded flattened head dims (fallback to replicated handled by the
    rules engine when head counts don't divide the mesh).
    """
    if cfg.mla is not None:
        return mla_specs(cfg)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    src = kv_src_dim if kv_src_dim is not None else d
    s: Dict[str, ParamSpec] = {
        "wq": ParamSpec((d, qd), ("embed", "heads")),
        "wk": ParamSpec((src, kvd), ("embed", "kv_heads")),
        "wv": ParamSpec((src, kvd), ("embed", "kv_heads")),
        "wo": ParamSpec((qd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((qd,), ("heads",), "zeros")
        s["bk"] = ParamSpec((kvd,), ("kv_heads",), "zeros")
        s["bv"] = ParamSpec((kvd,), ("kv_heads",), "zeros")
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((cfg.head_dim,), (None,), "ones")
        s["k_norm"] = ParamSpec((cfg.head_dim,), (None,), "ones")
    return s


def mla_specs(cfg) -> Dict[str, ParamSpec]:
    """MLA projections: queries per head; one joint projection to the KV
    latent and the shared rotary key part; the latent's norm; the latent's
    expansion to per-head keys (no rope) and values; the output."""
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    return {
        "wq": ParamSpec((d, H * cfg.qk_head_dim), ("embed", "heads")),
        "wkv_a": ParamSpec((d, m.kv_lora_rank + m.rope_dim), ("embed", None)),
        "kv_norm": ParamSpec((m.kv_lora_rank,), (None,), "ones"),
        "wkv_b": ParamSpec((m.kv_lora_rank, H * (m.nope_dim + m.v_dim)),
                           (None, "heads")),
        "wo": ParamSpec((H * m.v_dim, d), ("heads", "embed")),
    }


def softmax_scale(cfg) -> float:
    """Scale of the attention scores: ``qk_head_dim ** -0.5``, times YaRN's
    ``mscale(factor, mscale_all_dim) ** 2`` where the config scales rope."""
    scale = cfg.qk_head_dim ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def _rope(cfg, x, positions):
    cos, sin = rope_freqs(positions, x.shape[-1], cfg.rope_theta, cfg.yarn)
    return apply_rope(x, cos, sin)


# --------------------------------------------------------------------------- #
# Projections
# --------------------------------------------------------------------------- #
def project_q(cfg, p, x, positions=None):
    B, S, _ = x.shape
    if cfg.mla is not None:
        q = (x @ p["wq"].astype(x.dtype)).reshape(
            B, S, cfg.n_heads, cfg.qk_head_dim)
        nope = cfg.mla.nope_dim
        return jnp.concatenate(
            [q[..., :nope], _rope(cfg, q[..., nope:], positions)], -1)
    q = x @ p["wq"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    if positions is not None and cfg.pos_embedding == "rope":
        cos, sin = rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
    return q


def project_kv(cfg, p, x, positions=None):
    B, S, _ = x.shape
    if cfg.mla is not None:
        m, H = cfg.mla, cfg.n_heads
        kv_a = x @ p["wkv_a"].astype(x.dtype)
        c = rmsnorm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
        k_pe = _rope(cfg, kv_a[..., None, m.kv_lora_rank:], positions)
        kv = (c @ p["wkv_b"].astype(x.dtype)).reshape(
            B, S, H, m.nope_dim + m.v_dim)
        k = jnp.concatenate(
            [kv[..., :m.nope_dim],
             jnp.broadcast_to(k_pe, (B, S, H, m.rope_dim))], -1)
        return k, kv[..., m.nope_dim:]
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if positions is not None and cfg.pos_embedding == "rope":
        cos, sin = rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
        k = apply_rope(k, cos, sin)
    return k, v


# --------------------------------------------------------------------------- #
# Masks
# --------------------------------------------------------------------------- #
def make_mask(Sq: int, Sk: int, *, causal: bool, window: int = 0,
              offset: int = 0):
    """(Sq, Sk) bool mask.  offset = absolute position of query 0 minus key 0."""
    qi = jnp.arange(Sq)[:, None] + offset
    ki = jnp.arange(Sk)[None, :]
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= ki <= qi
    if window:
        m &= ki > qi - window
    return m


# --------------------------------------------------------------------------- #
# Core SDPA (grouped-query, fp32 softmax)
# --------------------------------------------------------------------------- #
def sdpa(q, k, v, mask=None, scale=None):
    """q/k: (B,Sq|Sk,H|kvH,D), v: (B,Sk,kvH,Dv); returns (B,Sq,H,Dv).
    ``scale`` defaults to ``D ** -0.5``.

    GQA is computed grouped — q reshaped to (kvH, group) — so KV is never
    materialized H-wide (keeps HBM traffic and TP resharding minimal).
    """
    B, Sq, H, D = q.shape
    if _ATTN_IMPL == "pallas":
        if v.shape[-1] != D or (scale is not None and scale != D ** -0.5):
            raise NotImplementedError(
                "the Pallas flash kernel takes one head width and D**-0.5")
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, mask=mask)
    kvH = k.shape[2]
    G = H // kvH
    qg = q.reshape(B, Sq, kvH, G, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (D ** -0.5 if scale is None else scale)
    if mask is not None:
        while mask.ndim < scores.ndim:
            mask = mask[None]
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    return out.reshape(B, Sq, H, v.shape[-1])


_FLASH_REMAT = True


def set_flash_remat(on: bool) -> None:
    """Flash-style recompute: checkpoint the KV-chunk body so the backward
    re-materializes chunk scores instead of saving all of them (what the
    Pallas kernel does in VMEM).  Off = save-all (ablation)."""
    global _FLASH_REMAT
    _FLASH_REMAT = on


def _causal_self(Sq, Sk, causal, window_eff, q_offset) -> bool:
    """Causal self-attention with a static zero window and offset: the
    calls whose key blocks above the diagonal hold no visible score."""
    def zero(x):
        return isinstance(x, int) and x == 0
    return causal and Sq == Sk and zero(window_eff) and zero(q_offset)


def flash_blocks(Sq, Sk, chunk, *, causal=True, window_eff=0, q_offset=0):
    """The (query block, key block) pairs ``sdpa_flash`` scores, in blocks
    of ``min(chunk, Sk)`` rows, in the order it scores them.  Causal
    self-attention with a static zero window and offset visits each query
    block's diagonal block, then the key blocks below it: n(n+1)/2 of the
    n² blocks.  Any other call scores every query against every key
    block."""
    C = min(chunk, Sk)
    n = -(-Sk // C)
    if _causal_self(Sq, Sk, causal, window_eff, q_offset):
        return [(i, j) for i in range(n) for j in [i, *range(i)]]
    return [(i, j) for j in range(n) for i in range(-(-Sq // C))]


def sdpa_flash(q, k, v, *, causal=True, window_eff=0, chunk: int = 1024,
               q_offset=0, scale=None):
    """XLA flash-structured attention: online softmax over KV chunks —
    O(S·chunk) live scores instead of O(S²).  Causal self-attention scores
    query blocks against the key blocks on and below the diagonal only
    (``flash_blocks``); other calls scan all KV chunks with every query.
    window_eff may be a traced scalar (hymba per-layer global/sliding
    selection).  Values may be narrower than queries and keys (MLA: 128
    against 192); ``scale`` defaults to ``D ** -0.5`` of the query width."""
    B, Sq, H, D = q.shape
    Dv = v.shape[-1]
    Sk, kvH = k.shape[1], k.shape[2]
    G = H // kvH
    C = min(chunk, Sk)
    n = -(-Sk // C)
    pad = n * C - Sk
    diagonal = _causal_self(Sq, Sk, causal, window_eff, q_offset)
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        k, v = jnp.pad(k, padw), jnp.pad(v, padw)
        if diagonal:
            q = jnp.pad(q, padw)
    from repro.parallel.act import constrain
    qg = q.reshape(B, q.shape[1], kvH, G, D)
    # keep attention sequence-sharded end to end (SP through the mixer):
    # scores stay (B,kvH,G,Sq/tp,C) local, KV chunks replicate over 'model'
    # (tiny) — avoids the partitioner's seq<->head all-to-all reshard.
    qg = constrain(qg, "act_batch", "act_seq", None, None, None)
    kc = k.reshape(B, n, C, kvH, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, C, kvH, Dv).transpose(1, 0, 2, 3, 4)
    kc = constrain(kc, None, "act_batch", None, None, None)
    vc = constrain(vc, None, "act_batch", None, None, None)
    scale = D ** -0.5 if scale is None else scale

    def merge(carry, qb, kj, vj, valid):
        """Online-softmax step of one key block; carry None starts it."""
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kj,
                       preferred_element_type=jnp.float32) * scale
        if valid is not None:
            s = jnp.where(valid[None, None, None], s, -1e30)
        m_blk = s.max(-1, keepdims=True)
        m_new = m_blk if carry is None else jnp.maximum(carry[0], m_blk)
        p_ = jnp.exp(s - m_new)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p_.astype(vj.dtype), vj)
        if carry is None:
            return m_new, p_.sum(-1, keepdims=True), pv.astype(jnp.float32)
        m, l, acc = carry
        alpha = jnp.exp(m - m_new)
        return m_new, alpha * l + p_.sum(-1, keepdims=True), acc * alpha + pv

    def remat(f):
        if not _FLASH_REMAT:
            return f
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.nothing_saveable,
            prevent_cse=False)

    if diagonal:
        # query block i: the diagonal block (i, i), the only one with masked
        # scores (and, in the last, the padded keys), then a scan over the
        # key blocks below it, which every query of the block sees.
        blocks = flash_blocks(Sq, Sk, chunk)
        tri = jnp.arange(C)[None, :] <= jnp.arange(C)[:, None]
        step = remat(merge)
        outs = []
        for i in range(n):
            row = [j for qb_, j in blocks if qb_ == i]
            qb = constrain(qg[:, i * C:(i + 1) * C],
                           "act_batch", "act_seq", None, None, None)
            carry = step(None, qb, kc[row[0]], vc[row[0]], tri)
            if row[1:]:
                lo, hi = row[1], row[-1] + 1
                carry, _ = jax.lax.scan(
                    lambda c, kv, qb=qb: (step(c, qb, *kv, None), None),
                    carry, (kc[lo:hi], vc[lo:hi]))
            m, l, acc = carry
            outs.append(acc / jnp.maximum(l, 1e-30))
        out = jnp.concatenate(outs, axis=3)[:, :, :, :Sq]
    else:
        qi = (jnp.arange(Sq) + q_offset)[:, None]             # (Sq, 1)

        def body(carry, inp):
            j, kj, vj = inp
            ki = j * C + jnp.arange(C)[None, :]               # (1, C)
            valid = ki < Sk
            if causal:
                valid &= ki <= qi
            if not (isinstance(window_eff, int) and window_eff == 0):
                w = window_eff
                valid &= (w == 0) | (ki > qi - w)
            return merge(carry, qg, kj, vj, valid), None

        m0 = jnp.full((B, kvH, G, Sq, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, kvH, G, Sq, 1), jnp.float32)
        a0 = jnp.zeros((B, kvH, G, Sq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            remat(body), (m0, l0, a0), (jnp.arange(n), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)


def sdpa_stub(q, k, v):
    """Shape-preserving near-zero-traffic stand-in used by the dry-run's
    kernel-adjustment methodology (EXPERIMENTS.md §Perf): attention traffic
    = T(real lowering) - T(stub lowering); the Pallas kernel's true HBM
    traffic (q,k,v,o block sweeps) is added back analytically."""
    B, Sq, H, D = q.shape
    kvH = k.shape[2]
    pooled = v.mean(axis=1, keepdims=True)               # (B,1,kvH,D)
    out = jnp.repeat(pooled, H // kvH, axis=2)           # (B,1,H,D)
    return jnp.broadcast_to(out, (B, Sq, H, D)) + 0.0 * q


def sdpa_auto(q, k, v, *, causal, window_eff=0, q_offset=0, mask=None,
              scale=None):
    """Dispatch: chunked flash structure for multi-token attention, naive
    masked SDPA otherwise (decode / explicit masks / pallas)."""
    if _ATTN_IMPL == "stub" and q.shape[1] > 1:
        return sdpa_stub(q, k, v)
    if (_ATTN_IMPL == "chunked" and q.shape[1] > 1 and mask is None):
        return sdpa_flash(q, k, v, causal=causal, window_eff=window_eff,
                          q_offset=q_offset, scale=scale)
    if mask is None:
        Sq, Sk = q.shape[1], k.shape[1]
        qi = (jnp.arange(Sq) + q_offset)[:, None]
        ki = jnp.arange(Sk)[None, :]
        mask = jnp.ones((Sq, Sk), bool)
        if causal:
            mask &= ki <= qi
        if not (isinstance(window_eff, int) and window_eff == 0):
            mask &= (window_eff == 0) | (ki > qi - window_eff)
    return sdpa(q, k, v, mask, scale)


def attention(cfg, p, x, positions, mask, kv_x=None, kv_positions=None,
              *, causal=False, window_eff=0):
    """Full self/cross attention for training & prefill.  Returns (B,S,d).

    mask=None + causal/window_eff semantics -> flash-structured path;
    an explicit mask array forces the naive path.  MLA runs under the
    ``mla`` named scope.
    """
    scope = (jax.named_scope("mla") if cfg.mla is not None
             else contextlib.nullcontext())
    with scope:
        q = project_q(cfg, p, x, positions)
        src = kv_x if kv_x is not None else x
        kpos = None if kv_x is not None else positions
        if kv_x is not None and kv_positions is not None:
            kpos = kv_positions
        k, v = project_kv(cfg, p, src, kpos)
        out = sdpa_auto(q, k, v, causal=causal, window_eff=window_eff,
                        mask=mask, scale=softmax_scale(cfg))
        B, S = x.shape[:2]
        return out.reshape(B, S, -1) @ p["wo"].astype(x.dtype)


# --------------------------------------------------------------------------- #
# Decode over caches
# --------------------------------------------------------------------------- #
def cache_update(k_cache, v_cache, k_new, v_new, pos, *, ring: bool):
    """Insert (B,1,kvH,D) entries at pos (ring: pos % window)."""
    W = k_cache.shape[1]
    idx = jax.lax.rem(pos, W) if ring else pos
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new.astype(k_cache.dtype), (0, idx, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new.astype(v_cache.dtype), (0, idx, 0, 0))
    return k_cache, v_cache


def decode_attention(cfg, p, x, pos, k_cache, v_cache, *, ring: bool,
                     is_global=None):
    """One-token decode: x (B,1,d), caches (B,W,kvH,D).  Returns out, caches.

    ring=True -> sliding-window ring buffer (cache positions are pos%W).
    is_global: optional traced bool (hymba): when True the window constraint
    is dropped (only meaningful for non-ring full-length caches).
    """
    B = x.shape[0]
    W = k_cache.shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = project_q(cfg, p, x, positions)
    k_new, v_new = project_kv(cfg, p, x, positions)
    k_cache, v_cache = cache_update(k_cache, v_cache, k_new, v_new, pos,
                                    ring=ring)
    if ring:
        slot_pos = pos - jax.lax.rem(pos - jnp.arange(W, dtype=jnp.int32)
                                     + W, jnp.int32(W))
        valid = (slot_pos >= 0) & (slot_pos <= pos)
        if cfg.window:
            valid &= slot_pos > pos - cfg.window
    else:
        kpos = jnp.arange(W, dtype=jnp.int32)
        valid = kpos <= pos
        if cfg.window:
            win_ok = kpos > pos - cfg.window
            if is_global is not None:
                win_ok = win_ok | is_global
            valid &= win_ok
    out = sdpa(q, k_cache, v_cache, valid[None, None, :], softmax_scale(cfg))
    out = out.reshape(B, 1, -1) @ p["wo"].astype(x.dtype)
    return out, k_cache, v_cache
