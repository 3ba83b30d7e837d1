"""shard_map MoE layer: the §Perf-identified fix for grok-class models.

Under pjit, the sort+scatter capacity dispatch defeats the SPMD partitioner
(it replicates the global (E·C, d) buffer over 'model' and all-reduces it —
and its fp32 backward — every layer; see EXPERIMENTS.md §Perf G1–G3).
This module FORCES the production layout with shard_map:

  * tokens stay on their device: (B/data, S/model, d) block per device;
  * every device holds all experts' TP shards (expert_ffn over 'model'),
    so routing is PURELY LOCAL with per-device capacity;
  * the only communication is one psum over 'model' of the expert-output
    partial sums — ~d·tokens_local bytes/layer instead of the ~E·C·d
    buffer coherence traffic.

Enabled via ``set_moe_dispatch("shard_map")`` (dry-run: --moe-dispatch).
Differentiable (shard_map + psum transpose); validated against the pjit
scatter path in tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_DISPATCH = "scatter"


def set_moe_dispatch(mode: str) -> None:
    assert mode in ("scatter", "shard_map"), mode
    global _DISPATCH
    _DISPATCH = mode


def get_moe_dispatch() -> str:
    return _DISPATCH


def moe_forward_shard_map(cfg, p, x, gates, idx, mesh, batch_axes,
                          tp_axis: str = "model"):
    """x: (B, S, d); gates/idx: (B, S, k).  Returns (B, S, d).

    Layout (grok-style TP experts — expert_ffn sharded over `tp_axis`):
    tokens are batch-sharded over the data axes and REPLICATED over the TP
    axis inside this region (every TP peer must see every token of its
    group, since each holds only h/TP of every expert); each device routes
    its group's tokens locally against its h-shard, and one psum over the
    TP axis completes the wd contraction.  EP-sharded experts (deepseek) use
    the pjit scatter path (asserted).
    """
    from repro.models.moe import _dispatch_combine_local

    m = cfg.moe
    d = cfg.d_model
    tp = tp_axis in mesh.shape and mesh.shape[tp_axis] > 1
    ep = tp and m.n_experts % mesh.shape[tp_axis] == 0
    assert not ep, \
        "shard_map dispatch supports TP-expert layouts (EP uses scatter)"
    bspec = (batch_axes if len(batch_axes) > 1
             else (batch_axes[0] if batch_axes else None))
    # tokens: data-sharded batch, seq REPLICATED over the TP axis
    x_spec = P(bspec, None, None)
    g_spec = P(bspec, None, None)
    # expert weights: (E, d, h) TP-sharded on the expert hidden dim
    sspec = tp_axis if tp else None
    w_spec = P(None, None, sspec)
    wd_spec = P(None, sspec, None)

    def body(xb, gb, ib, wg, wu, wd):
        Bl, Sl, _ = xb.shape
        xf = xb.reshape(Bl * Sl, d)
        pp = {"wg": wg, "wu": wu, "wd": wd}
        out = _dispatch_combine_local(cfg, pp, xf,
                                      gb.reshape(Bl * Sl, -1),
                                      ib.reshape(Bl * Sl, -1))
        if tp:
            # wd contraction ran over the local h shard -> partial sums
            out = jax.lax.psum(out, tp_axis)
        return out.reshape(Bl, Sl, d)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(x_spec, g_spec, g_spec, w_spec, w_spec,
                                 wd_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(x, gates.astype(x.dtype), idx, p["wg"].astype(x.dtype),
              p["wu"].astype(x.dtype), p["wd"].astype(x.dtype))
