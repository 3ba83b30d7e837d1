"""Plain reference of a Qwen3 dense decoder's training steps, in jax.numpy.

Written from the published architecture (Qwen3 technical report and the
model's ``config.json``) and shares no code with the program: token
embedding; per layer a pre-norm block of grouped-query attention with
RMSNorm on each query and key head before rotary embedding (rotate-half
form, ``rope_theta``), causal softmax at ``head_dim ** -0.5``, then a SiLU
gated MLP; a final RMSNorm and the LM head tied to the embedding.  The loss
is the mean cross-entropy over labelled positions plus ``z_loss_weight``
times the mean squared log-partition; AdamW with global-norm clipping,
linear warm-up then cosine decay, weight decay on every parameter.

Weights are made by `init_params` from the seed's key (`seed_key`), for
the program and for this reference alike: normal with the configuration's
``initializer_range`` for matrices and the embedding, ones for the norms.

``precision="float32"`` computes every product in float32 at the highest
matrix precision: the reference.  ``precision="float8"`` rounds both
operands of every matrix product to float8 (e4m3, per-tensor scaled) with
float32 accumulation, gradients passed straight through the rounding: the
control, one precision below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #
def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Flat ``path -> shape`` of the parameters, layers stacked on axis 0."""
    d, dff, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    qd = cfg["num_attention_heads"] * hd
    kvd = cfg["num_key_value_heads"] * hd
    return {
        "embed": (V, d), "final_norm/w": (d,),
        "g0/ln1/w": (L, d), "g0/ln2/w": (L, d),
        "g0/attn/wq": (L, d, qd), "g0/attn/wk": (L, d, kvd),
        "g0/attn/wv": (L, d, kvd), "g0/attn/wo": (L, qd, d),
        "g0/attn/q_norm": (L, hd), "g0/attn/k_norm": (L, hd),
        "g0/ffn/wg": (L, d, dff), "g0/ffn/wu": (L, d, dff),
        "g0/ffn/wd": (L, dff, d),
    }


def seed_key(seed: int):
    """A threefry key from any whole number up to 2**62.  Programs take it
    as an argument, so that one compiled program serves every seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def init_leaf(cfg: dict, key, path: str, shape: tuple):
    if path.endswith("norm/w") or path.endswith("_norm"):
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(key, zlib.crc32(path.encode()) >> 1)
    return cfg["initializer_range"] * jax.random.normal(key, shape,
                                                        jnp.float32)


def init_params(cfg: dict, key) -> Dict[str, jnp.ndarray]:
    """Flat ``path -> array`` from the seed's key; call under ``jax.jit``
    to make them on the device in one program."""
    return {p: init_leaf(cfg, key, p, s)
            for p, s in param_shapes(cfg).items()}


# --------------------------------------------------------------------------- #
# the model
# --------------------------------------------------------------------------- #
def _dot(precision: str):
    if precision == "float32":
        def dot(spec, a, b):
            return jnp.einsum(spec, a, b,
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    elif precision == "float8":
        def f8(x):
            # the value rounded to float8 under a per-tensor scale that maps
            # its largest magnitude to float8's largest (448), as float8
            # training recipes do; the gradient passed straight on
            s = jax.lax.stop_gradient(
                jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
            r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            return x + jax.lax.stop_gradient(r - x)

        def dot(spec, a, b):
            return jnp.einsum(spec, f8(a), f8(b),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return dot


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate-half rotary embedding of x (B, S, H, D) at positions 0..S-1."""
    S, D = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(dot, q, k, v, block: int):
    """Causal softmax attention of q over k and v, all (B, S, H, D), for
    ``block`` queries at a time, each block recomputed in the backward
    pass, so that no (S, S) matrix of scores exists whole."""
    B, S, H, D = q.shape
    block = block if S % block == 0 else S
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        qb, i = args
        s = dot("bqhd,bkhd->bhqk", qb, k) * D ** -0.5
        seen = kpos[None, :] <= (i * block + jnp.arange(block))[:, None]
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return dot("bhqk,bkhd->bqhd", a, v)

    qs = jnp.moveaxis(q.reshape(B, S // block, block, H, D), 1, 0)
    o = jax.lax.map(one, (qs, jnp.arange(S // block)))
    return jnp.moveaxis(o, 0, 1).reshape(B, S, H * D)


def hidden(cfg: dict, p: dict, tokens, precision: str, block: int = 512):
    """Final normed hidden states (B, S, d).  Each layer is recomputed in
    the backward pass, so that only its input is kept."""
    dot = _dot(precision)
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    B, S = tokens.shape

    @jax.checkpoint
    def layer(x, w):
        h = rmsnorm(x, w["ln1/w"], eps)
        q = dot("bsd,de->bse", h, w["attn/wq"]).reshape(B, S, H, hd)
        k = dot("bsd,de->bse", h, w["attn/wk"]).reshape(B, S, KV, hd)
        v = dot("bsd,de->bse", h, w["attn/wv"]).reshape(B, S, KV, hd)
        q = rope(rmsnorm(q, w["attn/q_norm"], eps), cfg["rope_theta"])
        k = rope(rmsnorm(k, w["attn/k_norm"], eps), cfg["rope_theta"])
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        o = attend(dot, q, k, v, block)
        x = x + dot("bse,ed->bsd", o, w["attn/wo"])
        h = rmsnorm(x, w["ln2/w"], eps)
        g = jax.nn.silu(dot("bsd,df->bsf", h, w["ffn/wg"]))
        u = dot("bsd,df->bsf", h, w["ffn/wu"])
        return x + dot("bsf,fd->bsd", g * u, w["ffn/wd"])

    x = p["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, {k[3:]: a[i] for k, a in p.items()
                      if k.startswith("g0/")})
    return rmsnorm(x, p["final_norm/w"], eps)


def loss_fn(cfg: dict, train: dict, p: dict, tokens, labels,
            precision: str, chunks: int):
    """Mean cross-entropy plus z-loss; the head runs over ``chunks`` blocks
    of rows, each recomputed in the backward pass, so its logits never
    exist whole."""
    dot = _dot(precision)
    x = hidden(cfg, p, tokens, precision)
    d = x.shape[-1]
    xs = x.reshape(chunks, -1, d)
    ys = labels.reshape(chunks, -1)

    @jax.checkpoint
    def block(args):
        xb, yb = args
        logits = dot("td,vd->tv", xb, p["embed"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = yb != -100
        ll = jnp.take_along_axis(logits, jnp.where(valid, yb, 0)[:, None],
                                 axis=-1)[:, 0]
        return (jnp.sum(jnp.where(valid, lse - ll, 0.0)),
                jnp.sum(jnp.where(valid, lse * lse, 0.0)),
                jnp.sum(valid))

    ce, z, n = jax.lax.map(block, (xs, ys))
    n = jnp.maximum(jnp.sum(n), 1)
    return jnp.sum(ce) / n + train["z_loss_weight"] * jnp.sum(z) / n


def lr_at(train: dict, step):
    """Learning rate of 1-based ``step``."""
    warm = jnp.minimum(step / max(train["warmup_steps"], 1), 1.0)
    t = jnp.clip((step - train["warmup_steps"])
                 / max(train["schedule_steps"] - train["warmup_steps"], 1),
                 0.0, 1.0)
    lo = train["min_lr_frac"]
    return train["lr"] * warm * (lo + (1 - lo) * 0.5 * (1 + jnp.cos(jnp.pi * t)))


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


def leaf_norms(tree: dict) -> Dict[str, jnp.ndarray]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in tree.items()}


def reference_readings(cfg: dict, train: dict, seed: int, batches,
                       precision: str = "float32", chunks: int = 4,
                       shard=None) -> Tuple[list, dict, dict]:
    """Losses of the first ``len(batches)`` steps from the seed's weights,
    the per-leaf norms of the first step's clipped gradient, and the
    per-leaf norms of the parameters' change over all the steps.

    ``shard`` (optional) places each array: a function of (path, shape)
    returning a sharding, for a reference spread over several chips."""
    shapes = param_shapes(cfg)
    out_shard = None
    if shard is not None:
        out_shard = {k: shard(k, s) for k, s in shapes.items()}
    key = seed_key(seed)
    p0 = jax.jit(lambda k: init_params(cfg, k), out_shardings=out_shard)(key)
    zeros = jax.jit(lambda: {k: jnp.zeros(s, jnp.float32)
                             for k, s in shapes.items()},
                    out_shardings=out_shard)
    m, v = zeros(), zeros()
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda p, m, v, t, x, y: train_step(
            cfg, train, precision, chunks, p, m, v, t, x, y),
            donate_argnums=(0, 1, 2))
        delta = jax.jit(lambda p, key: leaf_norms(
            {k: p[k] - init_leaf(cfg, key, k, s)
             for k, s in shapes.items()}))
        losses, g1 = [], None
        p = p0
        del p0
        for i, b in enumerate(batches):
            p, m, v, loss, g = step(p, m, v, jnp.float32(i + 1),
                                    jnp.asarray(b["tokens"]),
                                    jnp.asarray(b["labels"]))
            losses.append(float(loss))
            if i == 0:
                g1 = {k: float(x) for k, x in g.items()}
        dn = {k: float(x) for k, x in delta(p, key).items()}
    return losses, g1, dn
