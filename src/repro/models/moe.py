"""Mixture-of-experts block: top-k router (softmax or deepseek-v3 sigmoid),
shared experts, the per-sequence load-balancing auxiliary loss, and two
dispatches, chosen by the config:

* capacity (``capacity_factor`` set): sort + scatter into (E, C) slots,
  overflow to a trash slot (token-dropping — the padded grouped GEMM the
  paper's platform uses, §VII-C).  Expert compute is an (E, C, d) x
  (E, d, h) batched matmul — sharded expert-parallel over 'model' when E
  divides the axis, else TP over the expert hidden dim (grok-1: 8 experts on
  a 16-way axis).  The Pallas grouped-GEMM kernel in
  ``repro.kernels.moe_gemm`` implements the same contraction for TPU.
* dropless (``capacity_factor=None``): every assignment to an expert this
  device holds (``MoEConfig.held``) reaches it, whatever the imbalance.
  The router scores every expert; the layer computes the held experts' part
  of the result, as one chip of an expert-parallel layer does (on one chip,
  without the exchange).  Grouped products (``jax.lax.ragged_dot``) run
  over the held experts' rows only.

The dropless layer works under the named scopes ``moe.route``,
``moe.dispatch``, ``moe.experts``, ``moe.combine`` and ``moe.shared``, and
counts ``moe_rows_held`` (assignments to held experts: the rows its grouped
products compute) beside the auxiliary loss.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, activation_fn
from repro.models.mlp import mlp, mlp_specs


def moe_specs(cfg) -> Dict[str, ParamSpec]:
    m = cfg.moe
    d, E, h, n = cfg.d_model, m.n_experts, m.d_expert, m.n_held
    s: Dict[str, ParamSpec] = {
        "router": ParamSpec((d, E), ("embed", "experts"), "normal", 0.02),
        "wg": ParamSpec((n, d, h), ("experts", "embed", "expert_ffn")),
        "wu": ParamSpec((n, d, h), ("experts", "embed", "expert_ffn")),
        "wd": ParamSpec((n, h, d), ("experts", "expert_ffn", "embed")),
    }
    if m.n_shared:
        # shared experts are always-on: computed as one fused wide MLP
        s["shared"] = {
            "wg": ParamSpec((d, m.n_shared * h), ("embed", "ffn")),
            "wu": ParamSpec((d, m.n_shared * h), ("embed", "ffn")),
            "wd": ParamSpec((m.n_shared * h, d), ("ffn", "embed")),
        }
    return s


def _route(cfg, logits):
    """logits (B, S, E) -> (gates (B,S,k), idx (B,S,k), aux_loss scalar).

    The auxiliary loss is DeepSeek's expert-level balance loss, taken per
    sequence and averaged over the batch: ``weight * sum_e f_e * P_e``, with
    ``f_e = E * count_e / (S * k)`` from the sequence's assignments and
    ``P_e`` its mean router probability."""
    m = cfg.moe
    if m.router == "sigmoid":                      # deepseek-v3 style
        scores = jax.nn.sigmoid(logits)
        gates, idx = jax.lax.top_k(scores, m.top_k)
        probs = scores / (scores.sum(-1, keepdims=True) + 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    S = logits.shape[1]
    counts = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32).sum((1, 2))
    f_e = counts * m.n_experts / (S * m.top_k)              # (B, E)
    aux = m.aux_loss_weight * jnp.mean(jnp.sum(f_e * probs.mean(1), -1))
    return gates, idx, aux


def capacity(cfg, n_tokens: int) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)                  # round up to 8


def _dispatch_combine_local(cfg, p, xs, gates, idx):
    """Per-shard dispatch -> padded expert GEMMs -> combine.

    xs: (T_loc, d); gates/idx: (T_loc, k).  Purely local slot assignment —
    the production layout: capacity is PER DATA SHARD, so the scatter never
    crosses the data axis (a replicated global buffer forces the partitioner
    into per-layer all-reduces of the whole capacity buffer).
    """
    m = cfg.moe
    T, d = xs.shape
    E, k = m.n_experts, m.top_k
    C = capacity(cfg, T)

    flat_e = idx.reshape(T * k)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    flat_g = gates.reshape(T * k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    keep = pos < C
    dest = jnp.where(keep, se * C + pos, E * C)            # E*C = trash slot

    buf = jnp.zeros((E * C + 1, d), xs.dtype).at[dest].set(xs[st])
    eb = buf[: E * C].reshape(E, C, d)

    # ---- grouped expert GEMMs (padded — balanced compute, paper §VII-C) ----
    act = activation_fn(cfg.activation)
    h = act(jnp.einsum("ecd,edh->ech", eb, p["wg"].astype(xs.dtype)))
    h = h * jnp.einsum("ecd,edh->ech", eb, p["wu"].astype(xs.dtype))
    y = jnp.einsum("ech,ehd->ecd", h, p["wd"].astype(xs.dtype))

    # ---- combine: gather back, gate-weight, sum the k contributions --------
    yflat = jnp.concatenate([y.reshape(E * C, d),
                             jnp.zeros((1, d), xs.dtype)], 0)
    back = yflat[dest] * sg[:, None].astype(xs.dtype)
    return jnp.zeros((T, d), xs.dtype).at[st].add(back)


def _dropless(cfg, p, xs, gates, idx):
    """Every assignment to a held expert, through grouped products over the
    held experts' rows.

    xs: (T, d); gates/idx: (T, k).  Each of the T*k assignments gets a row
    of its own in a buffer laid out by group: the held experts' rows first,
    expert after expert, then the assignments to experts held elsewhere,
    which no product reads.  The buffer holds all T*k rows, so no imbalance
    can overflow it.  Returns the routed part of the layer's output (T, d)
    and the rows held.

    The grouped products define no row outside their groups (XLA's TPU
    ragged dot leaves such rows, and their cotangents, as they find them),
    so every value read past the groups is selected away, never
    multiplied by zero.
    """
    m = cfg.moe
    T, d = xs.shape
    k = m.top_k
    first, n = m.held_range
    A = T * k
    with jax.named_scope("moe.dispatch"):
        e = idx.reshape(A) - first
        held = (e >= 0) & (e < n)
        group = jnp.where(held, e, n)              # group n: held elsewhere
        one_hot = jax.nn.one_hot(group, n + 1, dtype=jnp.int32)
        rank = jnp.sum((jnp.cumsum(one_hot, 0) - 1) * one_hot, 1)
        sizes = one_hot.sum(0)[:n]
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(sizes)])
        dest = starts[group] + rank
        rows = jnp.where(held[:, None], jnp.repeat(xs, k, axis=0), 0)
        buf = jnp.zeros((A, d), xs.dtype).at[dest].set(
            rows, unique_indices=True)
    with jax.named_scope("moe.experts"):
        dt = xs.dtype
        live = (jnp.arange(A) < starts[n])[:, None]

        def grouped(lhs, w):
            return jnp.where(
                live, jax.lax.ragged_dot(lhs, w.astype(dt), sizes), 0)

        act = activation_fn(cfg.activation)
        h = act(grouped(buf, p["wg"])) * grouped(buf, p["wu"])
        y = grouped(h, p["wd"])
    with jax.named_scope("moe.combine"):
        yk = jnp.where(held.reshape(T, k, 1),
                       y.at[dest].get(unique_indices=True).reshape(T, k, d),
                       0)
        out = jnp.sum(yk.astype(jnp.float32) * gates.reshape(T, k, 1),
                      axis=1)
    return out.astype(dt), starts[n].astype(jnp.float32)


def stat_names(cfg) -> Tuple[str, ...]:
    """Names of the scalars ``moe_forward`` returns for ``cfg``."""
    if cfg.moe.capacity_factor is None:
        return ("aux_loss", "moe_rows_held")
    return ("aux_loss",)


def moe_forward(cfg, p, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (out (B, S, d), {name: scalar} of ``stat_names``)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)

    with jax.named_scope("moe.route"):
        logits = (xf.astype(jnp.float32)
                  @ p["router"].astype(jnp.float32))
        gates, idx, aux = _route(cfg, logits.reshape(B, S, -1))
        gates, idx = gates.reshape(T, -1), idx.reshape(T, -1)
    stats = {"aux_loss": aux}

    if m.capacity_factor is None:
        out, stats["moe_rows_held"] = _dropless(cfg, p, xf, gates, idx)
    else:
        if m.held is not None:
            raise ValueError("held experts need dropless routing "
                             "(capacity_factor=None)")
        from repro.parallel.moe_shard_map import get_moe_dispatch
        from repro.parallel.act import _state
        mesh = getattr(_state, "mesh", None)
        rules = getattr(_state, "rules", None)
        if get_moe_dispatch() == "shard_map" and mesh is not None:
            from repro.parallel.moe_shard_map import moe_forward_shard_map
            out = moe_forward_shard_map(
                cfg, p, x, gates.reshape(B, S, -1), idx.reshape(B, S, -1),
                mesh, rules.get("act_batch", ()) if rules else ())
            out = out.reshape(T, d)
        else:
            # global-capacity pjit dispatch (per-data-shard vmapped dispatch
            # was measured NET-NEGATIVE on the 16x16 mesh — EXPERIMENTS.md
            # §Perf G2/G3: the partitioner replicates the vmapped scatter's
            # backward); the forced-local shard_map layout is G5.
            out = _dispatch_combine_local(cfg, p, xf, gates, idx)

    if m.n_shared:
        with jax.named_scope("moe.shared"):
            out = out + mlp(cfg, p["shared"], xf)
    return out.reshape(B, S, d), stats


def moe_or_mlp_specs(cfg, layer_is_dense: bool):
    if cfg.moe is None or layer_is_dense:
        d_ff = (cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.d_ff_dense)
                else cfg.d_ff)
        return mlp_specs(cfg, d_ff)
    return moe_specs(cfg)
