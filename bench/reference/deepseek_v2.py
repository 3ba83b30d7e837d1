"""Plain reference of DeepSeek-V2(-Lite)'s training steps, in jax.numpy, for
one chip's share of an expert-parallel layer.

Written from the published architecture (DeepSeek-V2, arXiv:2405.04434, and
the model's ``config.json``) and shares no code with the program.  Token
embedding; per layer a pre-norm block of multi-head latent attention, then
a SiLU gated MLP (the first ``first_k_dense_replace`` layers) or an expert
layer; a final RMSNorm and an untied LM head.

Latent attention, per token with ``h`` the normed input:
``q = h Wq`` per head (``qk_nope_head_dim`` dims without rope, then
``qk_rope_head_dim`` with); ``[c, k_pe] = h Wkv_a``; the latent ``c`` is
RMS-normed and expanded, ``[k_nope, v] = c Wkv_b`` per head; ``k_pe`` is one
rotary key shared by every head.  Rope is YaRN's, as DeepSeek-V2's
``DeepseekV2YarnRotaryEmbedding`` computes it, in the rotate-half form (the
published weights store the rope columns interleaved: with random weights a
fixed permutation of columns).  Scores are causal, scaled by
``(nope + rope) ** -0.5 * mscale(factor, mscale_all_dim) ** 2``.

The expert layer routes by a float32 softmax over every published expert
and keeps the top ``num_experts_per_tok`` greedily, renormalised only where
``norm_topk_prob``.  This chip holds experts ``[first_held_expert,
first_held_expert + n_routed_experts)``; each of them runs over every token,
weighted by its router probability where the token chose it and by 0
elsewhere, times ``routed_scaling_factor``; the shared experts run as one
SwiGLU of ``n_shared_experts`` times the expert width.  The auxiliary loss
is the expert-level balance loss per sequence (``seq_aux``): ``alpha *
sum_e f_e P_e``, ``f_e = E * count_e / (S * k)``, averaged over the batch.
The loss is the mean cross-entropy plus ``z_loss_weight`` times the mean
squared log-partition plus the layers' auxiliary losses; AdamW as in
``bench/reference/qwen3.py``.

Weights come from ``init_leaf`` of ``bench/reference/qwen3.py`` for each
path of `param_shapes`, for the program and for this reference alike.
``precision`` is as there: ``"float32"`` the reference, ``"float8"`` the
control with every matrix product's operands rounded to float8.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference.qwen3 import (_dot, init_leaf, leaf_norms, lr_at,
                                   rmsnorm, seed_key)


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #
def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Flat ``path -> shape`` of the parameters: group ``g0`` the leading
    dense layers, ``g1`` the expert layers, layers stacked on axis 0."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    V, k = cfg["vocab_size"], cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - k
    E, h = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    sh, dff = cfg["n_shared_experts"] * h, cfg["intermediate_size"]
    routers = cfg["published"]["n_routed_experts"]

    def block(g, L):
        return {f"{g}/ln1/w": (L, d), f"{g}/ln2/w": (L, d),
                f"{g}/attn/wq": (L, d, H * (nope + rope)),
                f"{g}/attn/wkv_a": (L, d, r + rope),
                f"{g}/attn/kv_norm": (L, r),
                f"{g}/attn/wkv_b": (L, r, H * (nope + vd)),
                f"{g}/attn/wo": (L, H * vd, d)}

    shapes = {"embed": (V, d), "final_norm/w": (d,), "lm_head": (d, V)}
    shapes.update(block("g0", k))
    shapes.update({"g0/ffn/wg": (k, d, dff), "g0/ffn/wu": (k, d, dff),
                   "g0/ffn/wd": (k, dff, d)})
    shapes.update(block("g1", n))
    shapes.update({"g1/ffn/router": (n, d, routers),
                   "g1/ffn/wg": (n, E, d, h), "g1/ffn/wu": (n, E, d, h),
                   "g1/ffn/wd": (n, E, h, d),
                   "g1/ffn/shared/wg": (n, d, sh),
                   "g1/ffn/shared/wu": (n, d, sh),
                   "g1/ffn/shared/wd": (n, sh, d)})
    return shapes


def init_params(cfg: dict, key) -> Dict[str, jnp.ndarray]:
    """Flat ``path -> array`` from the seed's key; call under ``jax.jit``
    to make them on the device in one program."""
    return {p: init_leaf(cfg, key, p, s)
            for p, s in param_shapes(cfg).items()}


# --------------------------------------------------------------------------- #
# YaRN rope
# --------------------------------------------------------------------------- #
def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: dict):
    """Frequencies of the ``qk_rope_head_dim`` rotary dims (dim pairs)."""
    y, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    L0 = y["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(L0 / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / y["factor"]
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def rope(cfg: dict, x):
    """Rotate-half YaRN rope of x (B, S, H, D) at positions 0..S-1."""
    y = cfg["rope_scaling"]
    amp = (yarn_mscale(y["factor"], y["mscale"])
           / yarn_mscale(y["factor"], y["mscale_all_dim"]))
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * yarn_inv_freq(cfg)[None, :])
    cos = (amp * jnp.cos(ang))[None, :, None, :]
    sin = (amp * jnp.sin(ang))[None, :, None, :]
    D = x.shape[3]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(cfg: dict) -> float:
    y = cfg["rope_scaling"]
    m = yarn_mscale(y["factor"], y["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


# --------------------------------------------------------------------------- #
# the model
# --------------------------------------------------------------------------- #
def attend(dot, q, k, v, scale: float, block: int):
    """Causal softmax attention, q/k (B, S, H, D) and v (B, S, H, Dv), for
    ``block`` queries at a time, each block recomputed in the backward
    pass; returns (B, S, H * Dv)."""
    B, S, H, D = q.shape
    block = block if S % block == 0 else S
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        qb, i = args
        s = dot("bqhd,bkhd->bhqk", qb, k) * scale
        seen = kpos[None, :] <= (i * block + jnp.arange(block))[:, None]
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return dot("bhqk,bkhd->bqhd", a, v)

    qs = jnp.moveaxis(q.reshape(B, S // block, block, H, D), 1, 0)
    o = jax.lax.map(one, (qs, jnp.arange(S // block)))
    return jnp.moveaxis(o, 0, 1).reshape(B, S, -1)


def mla(cfg: dict, dot, w: dict, h, block: int):
    """Multi-head latent attention of the normed input h (B, S, d)."""
    B, S, _ = h.shape
    H = cfg["num_attention_heads"]
    nope, rdim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = dot("bsd,de->bse", h, w["attn/wq"]).reshape(B, S, H, nope + rdim)
    kv_a = dot("bsd,de->bse", h, w["attn/wkv_a"])
    c = rmsnorm(kv_a[..., :r], w["attn/kv_norm"], cfg["rms_norm_eps"])
    k_pe = rope(cfg, kv_a[..., None, r:])
    kv = dot("bsr,re->bse", c, w["attn/wkv_b"]).reshape(B, S, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], rope(cfg, q[..., nope:])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rdim))], -1)
    o = attend(dot, q, k, kv[..., nope:], softmax_scale(cfg), block)
    return dot("bse,ed->bsd", o, w["attn/wo"])


def swiglu(dot, h, wg, wu, wd):
    g = jax.nn.silu(dot("bsd,df->bsf", h, wg))
    return dot("bsf,fd->bsd", g * dot("bsd,df->bsf", h, wu), wd)


def route(cfg: dict, dot, w: dict, h):
    """-> (the held experts' weights (B, S, E): the router probability where
    the token chose the expert, else 0; the layer's auxiliary loss)."""
    routers = cfg["published"]["n_routed_experts"]
    k, S = cfg["num_experts_per_tok"], h.shape[1]
    p = jax.nn.softmax(dot("bsd,de->bse", h, w["ffn/router"]), axis=-1)
    top, idx = jax.lax.top_k(p, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, routers, dtype=jnp.float32), -2)
    gate = chosen * p
    if cfg["norm_topk_prob"]:
        gate = gate / jnp.sum(top, -1, keepdims=True)
    f = jnp.sum(chosen, 1) * routers / (S * k)
    aux = cfg["aux_loss_alpha"] * jnp.mean(jnp.sum(f * jnp.mean(p, 1), -1))
    first = cfg["first_held_expert"]
    return gate[..., first:first + cfg["n_routed_experts"]], aux


def moe(cfg: dict, dot, w: dict, h):
    """The held experts' part of the expert layer plus the shared experts,
    in the dense form: every held expert over every token, one after
    another; returns (out, aux)."""
    gate, aux = route(cfg, dot, w, h)

    @jax.checkpoint
    def expert(out, e):
        wg, wu, wd, g = e
        return out + swiglu(dot, h, wg, wu, wd) * g[..., None], None

    out = jax.lax.scan(expert, jnp.zeros(h.shape, jnp.float32),
                       (w["ffn/wg"], w["ffn/wu"], w["ffn/wd"],
                        jnp.moveaxis(gate, -1, 0)))[0]
    out = cfg["routed_scaling_factor"] * out
    out = out + swiglu(dot, h, w["ffn/shared/wg"], w["ffn/shared/wu"],
                       w["ffn/shared/wd"])
    return out, aux


def hidden(cfg: dict, p: dict, tokens, precision: str, block: int = 512):
    """Final normed hidden states (B, S, d) and the summed auxiliary loss.
    The layers of each group run in a loop over their stacked weights, each
    recomputed in the backward pass, so that only its input is kept."""
    dot = _dot(precision)
    eps = cfg["rms_norm_eps"]

    def layer(dense):
        @jax.checkpoint
        def run(x, w):
            x = x + mla(cfg, dot, w, rmsnorm(x, w["ln1/w"], eps), block)
            h = rmsnorm(x, w["ln2/w"], eps)
            if dense:
                return x + swiglu(dot, h, w["ffn/wg"], w["ffn/wu"],
                                  w["ffn/wd"]), jnp.float32(0.0)
            f, aux = moe(cfg, dot, w, h)
            return x + f, aux
        return run

    def group(g):
        return {q[3:]: arr for q, arr in p.items() if q.startswith(g + "/")}

    x = p["embed"][tokens]
    x, aux0 = jax.lax.scan(layer(True), x, group("g0"))
    x, aux1 = jax.lax.scan(layer(False), x, group("g1"))
    return rmsnorm(x, p["final_norm/w"], eps), aux0.sum() + aux1.sum()


def loss_fn(cfg: dict, train: dict, p: dict, tokens, labels,
            precision: str, chunks: int):
    """Mean cross-entropy plus z-loss plus the auxiliary losses; the head
    runs over ``chunks`` blocks of rows, each recomputed in the backward
    pass, so its logits never exist whole."""
    dot = _dot(precision)
    x, aux = hidden(cfg, p, tokens, precision)
    d = x.shape[-1]
    xs = x.reshape(chunks, -1, d)
    ys = labels.reshape(chunks, -1)

    @jax.checkpoint
    def block(args):
        xb, yb = args
        logits = dot("td,dv->tv", xb, p["lm_head"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = yb != -100
        ll = jnp.take_along_axis(logits, jnp.where(valid, yb, 0)[:, None],
                                 axis=-1)[:, 0]
        return (jnp.sum(jnp.where(valid, lse - ll, 0.0)),
                jnp.sum(jnp.where(valid, lse * lse, 0.0)),
                jnp.sum(valid))

    ce, z, n = jax.lax.map(block, (xs, ys))
    n = jnp.maximum(jnp.sum(n), 1)
    return (jnp.sum(ce) / n + train["z_loss_weight"] * jnp.sum(z) / n
            + aux)


def train_step(cfg: dict, train: dict, precision: str, chunks: int,
               p: dict, m: dict, v: dict, step, tokens, labels):
    """One AdamW step; returns (p, m, v, loss, the clipped gradient's
    per-leaf norms)."""
    loss, g = jax.value_and_grad(
        lambda q: loss_fn(cfg, train, q, tokens, labels, precision, chunks)
    )(p)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    scale = jnp.minimum(1.0, train["grad_clip"] / (norm + 1e-9))
    g = {k: x * scale for k, x in g.items()}
    b1, b2, eps = train["beta1"], train["beta2"], train["eps"]
    lr = lr_at(train, step)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in p}
    v = {k: b2 * v[k] + (1 - b2) * g[k] * g[k] for k in p}
    p = {k: p[k] - lr * ((m[k] / c1) / (jnp.sqrt(v[k] / c2) + eps)
                         + train["weight_decay"] * p[k]) for k in p}
    return p, m, v, loss, leaf_norms(g)


def reference_readings(cfg: dict, train: dict, seed: int, batches,
                       precision: str = "float32", chunks: int = 4
                       ) -> Tuple[list, dict, dict]:
    """Losses of the first ``len(batches)`` steps from the seed's weights,
    the per-leaf norms of the first step's clipped gradient, and the
    per-leaf norms of the parameters' change over all the steps."""
    shapes = param_shapes(cfg)
    key = seed_key(seed)
    p = jax.jit(lambda k: init_params(cfg, k))(key)
    zeros = jax.jit(lambda: {k: jnp.zeros(s, jnp.float32)
                             for k, s in shapes.items()})
    m, v = zeros(), zeros()
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda p, m, v, t, x, y: train_step(
            cfg, train, precision, chunks, p, m, v, t, x, y),
            donate_argnums=(0, 1, 2))
        delta = jax.jit(lambda p, key: leaf_norms(
            {k: p[k] - init_leaf(cfg, key, k, s)
             for k, s in shapes.items()}))
        losses, g1 = [], None
        for i, b in enumerate(batches):
            p, m, v, loss, g = step(p, m, v, jnp.float32(i + 1),
                                    jnp.asarray(b["tokens"]),
                                    jnp.asarray(b["labels"]))
            losses.append(float(loss))
            if i == 0:
                g1 = {k: float(x) for k, x in g.items()}
        dn = {k: float(x) for k, x in delta(p, key).items()}
    return losses, g1, dn


def routing(cfg: dict, p: dict, tokens, precision: str = "float32"):
    """Each expert layer's top-k expert indices (B, S, k) over the seed's
    weights, for counting where the program's routing departs."""
    dot = _dot(precision)
    eps, k = cfg["rms_norm_eps"], cfg["first_k_dense_replace"]
    x = p["embed"][tokens]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        g, j = ("g0", i) if i < k else ("g1", i - k)
        w = {q[3:]: arr[j] for q, arr in p.items() if q.startswith(g + "/")}
        x = x + mla(cfg, dot, w, rmsnorm(x, w["ln1/w"], eps), 512)
        h = rmsnorm(x, w["ln2/w"], eps)
        if g == "g0":
            x = x + swiglu(dot, h, w["ffn/wg"], w["ffn/wu"], w["ffn/wd"])
        else:
            probs = jax.nn.softmax(dot("bsd,de->bse", h, w["ffn/router"]), -1)
            out.append(jax.lax.top_k(probs, cfg["num_experts_per_tok"])[1])
            x = x + moe(cfg, dot, w, h)[0]
    return out
