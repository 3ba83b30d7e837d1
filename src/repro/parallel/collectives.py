"""Explicit collective schedules via shard_map: the manual counterpart to
XLA's auto-inserted FSDP collectives.

``ring_all_gather`` is the ppermute ring (what runs on the ICI torus);
``fsdp_ffn_prefetch`` demonstrates software-pipelined C3: the all-gather for
layer i+1's weights is issued *before* layer i's compute so the scheduler can
overlap them — the explicit form of the paper's Fig 2 overlap window.  Used
by the multi-device tests and as a §Perf A/B against the auto schedule.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_all_gather(x, axis_name: str):
    """All-gather along axis_name via a bidirectional-naive ppermute ring.

    x: local shard (..., d).  Returns (axis_size, ..., d) stacked gathers in
    ring order, rotated so index 0 is rank 0's shard.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunks = [x]
    cur = x
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        chunks.append(cur)
    stacked = jnp.stack(chunks, 0)                    # [my, my-1, my-2, ...]
    # rotate into rank order: chunk j holds shard (idx - j) mod n
    order = (idx - jnp.arange(n)) % n
    return jnp.zeros_like(stacked).at[order].set(stacked)


def fsdp_ffn_prefetch(x, w_stacked, mesh: Mesh, *, fsdp_axis: str = "data"):
    """Scan an L-layer FFN whose weights are FSDP-sharded over `fsdp_axis`,
    all-gathering layer i+1's weights while layer i computes.

    x: (B_local, d) activations (already sharded by caller via shard_map);
    w_stacked: (L, d/axis, d) local weight shards.  Double-buffered carry:
    (x, gathered weights for the next layer).
    """
    L = w_stacked.shape[0]

    def gather_w(wl):
        g = ring_all_gather(wl, fsdp_axis)            # (n, d/n, d)
        return g.reshape(-1, g.shape[-1])             # (d, d)

    def body(carry, wl_next):
        x, w_cur = carry
        w_nxt = gather_w(wl_next)     # issued before the matmul -> overlaps
        x = jax.nn.relu(x @ w_cur)
        return (x, w_nxt), None

    w0 = gather_w(w_stacked[0])
    (x, w_last), _ = jax.lax.scan(body, (x, w0), w_stacked[1:])
    x = jax.nn.relu(x @ w_last)
    return x


def make_fsdp_prefetch_fn(mesh: Mesh, fsdp_axis: str = "data"):
    """shard_map-wrapped explicit-overlap FFN chain (for tests / A-B)."""
    fn = partial(fsdp_ffn_prefetch, mesh=mesh, fsdp_axis=fsdp_axis)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(fsdp_axis, None), P(None, fsdp_axis, None)),
        out_specs=P(fsdp_axis, None),
        check_vma=False)
