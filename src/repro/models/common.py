"""Shared model-building blocks: param specs, norms, activations, RoPE, loss.

Pure JAX (no flax).  A model is a tree of ``ParamSpec`` (single source of
truth for shape, logical sharding axes and initializer); ``init_params``
materializes arrays, ``logical_axes`` extracts the sharding tree that
``repro.parallel.sharding`` maps onto the mesh.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------- #
# Param specs
# --------------------------------------------------------------------------- #
class ParamSpec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis name per dim (None = replicated)
    init: str = "normal"              # normal | zeros | ones | embed
    scale: float = -1.0               # -1 -> 1/sqrt(fan_in)


def _fan_in(shape: Tuple[int, ...]) -> int:
    # stacked layer axes don't count toward fan-in
    return shape[-2] if len(shape) >= 2 else shape[-1]


def init_params(spec_tree, key, dtype=jnp.float32):
    leaves, treedef = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    out = []
    for spec, k in zip(leaves, keys):
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, dtype)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, dtype)
        else:
            scale = spec.scale
            if scale < 0:
                scale = 1.0 / math.sqrt(max(_fan_in(spec.shape), 1))
            if spec.init == "embed":
                scale = 0.02
            arr = (jax.random.normal(k, spec.shape, jnp.float32)
                   * scale).astype(dtype)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(spec_tree, dtype=jnp.float32):
    """ShapeDtypeStruct tree (no allocation) — used by the dry-run."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def logical_axes(spec_tree):
    return jax.tree_util.tree_map(
        lambda s: s.axes, spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def param_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


# --------------------------------------------------------------------------- #
# Norms / activations
# --------------------------------------------------------------------------- #
def rmsnorm(x, weight, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layernorm(x, weight, bias, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dt)


def norm_spec(cfg, d: int, prefix: Tuple[int, ...] = ()) -> Dict[str, ParamSpec]:
    lead = tuple(prefix)
    lead_ax = ("layers",) * len(prefix)
    if cfg.norm == "layernorm":
        return {"w": ParamSpec(lead + (d,), lead_ax + (None,), "ones"),
                "b": ParamSpec(lead + (d,), lead_ax + (None,), "zeros")}
    return {"w": ParamSpec(lead + (d,), lead_ax + (None,), "ones")}


def apply_norm(cfg, p, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["w"], cfg.norm_eps)


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "squared_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


def softcap(logits, cap: float):
    if cap and cap > 0:
        return cap * jnp.tanh(logits / cap)
    return logits


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(head_dim: int, theta: float, yarn):
    """DeepSeek-V2's YaRN frequencies: original ones ``f`` below the ramp,
    ``f / factor`` above it, blended linearly across it.  The ramp runs over
    the frequency indices between the dims that turn ``beta_fast`` and
    ``beta_slow`` times in ``original_max_len`` positions."""
    def dim_at(turns):
        return (head_dim * math.log(yarn.original_max_len
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim_at(yarn.beta_fast)), 0)
    hi = min(math.ceil(dim_at(yarn.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    f = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                        / head_dim)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    return f / yarn.factor * ramp + f * (1.0 - ramp)


def rope_freqs(positions, head_dim: int, theta: float, yarn=None):
    """cos/sin tables for given positions: (..., head_dim//2) each; with
    ``yarn`` (a ``YarnConfig``) its frequencies and cos/sin factor."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim))
        amp = 1.0
    else:
        inv = yarn_inv_freq(head_dim, theta, yarn)
        amp = (yarn_mscale(yarn.factor, yarn.mscale)
               / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv       # (..., hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if amp != 1.0:
        cos, sin = amp * cos, amp * sin
    return cos, sin


def apply_rope(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2)."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    cos, sin = cos[..., None, :], sin[..., None, :]             # head axis
    while cos.ndim < x.ndim:                                    # left-pad batch
        cos, sin = cos[None], sin[None]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------- #
# Loss
# --------------------------------------------------------------------------- #
def cross_entropy_loss(logits, labels, z_loss_weight: float = 0.0,
                       ignore_index: int = -100):
    """Mean CE over non-ignored tokens, with optional z-loss regularizer.

    logits: (..., V) any float dtype; labels: (...) int32.
    """
    logits = logits.astype(jnp.float32)
    mask = (labels != ignore_index)
    safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = (lse - ll) * mask
    denom = jnp.maximum(mask.sum(), 1)
    loss = ce.sum() / denom
    metrics = {"ce_loss": loss, "tokens": mask.sum()}
    if z_loss_weight:
        zl = z_loss_weight * jnp.sum(jnp.square(lse) * mask) / denom
        metrics["z_loss"] = zl
        loss = loss + zl
    return loss, metrics


# --------------------------------------------------------------------------- #
# Misc
# --------------------------------------------------------------------------- #
def pad_vocab(v: int, multiple: int = 128) -> int:
    """Pad vocab so TP over the production mesh divides evenly."""
    return -(-v // multiple) * multiple


def take_embedding(table, tokens):
    return jnp.take(table, tokens, axis=0)


def stack_specs(spec_tree, n: int):
    """Prepend a stacked 'layers' axis to every spec in a layer's spec tree."""
    def f(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale)
    return jax.tree_util.tree_map(
        f, spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def tree_get(tree, idx: int):
    """Index a stacked-params tree along axis 0 (for non-scan layer loops)."""
    return jax.tree_util.tree_map(lambda x: x[idx], tree)
