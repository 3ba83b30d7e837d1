"""Decoder-only LM covering the dense / MoE / hybrid (hymba) families.

Layers are grouped by structure signature (e.g. deepseek-moe's first dense
layer vs its 27 MoE layers) and each group runs under ``jax.lax.scan`` over
stacked params with per-layer ``jax.checkpoint`` — compile-time O(1) in depth,
activation memory O(L) in residuals only.  Decode uses full-length KV caches
for full-attention archs and ring buffers for sliding-window archs; hybrid
blocks additionally carry SSM states.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models.common import (ParamSpec, apply_norm, cross_entropy_loss,
                                 norm_spec, pad_vocab, softcap, stack_specs,
                                 take_embedding, tree_get)
from repro.models.moe import moe_forward, moe_or_mlp_specs, stat_names
from repro.models.mlp import mlp
from repro.parallel.act import shard_residual

REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


class DecoderOnlyLM:
    def __init__(self, cfg, *, max_cache_len: int = 0,
                 remat: str = "nothing", scan_layers: bool = True):
        self.cfg = cfg
        self.vp = pad_vocab(cfg.vocab_size)
        self.max_cache_len = max_cache_len or cfg.max_seq_len
        self.remat = remat
        self.scan_layers = scan_layers

    # ------------------------------------------------------------- structure
    def layer_groups(self) -> List[Tuple[int, bool]]:
        """[(n_layers, is_dense_mlp)] group split (moe first_k_dense)."""
        cfg = self.cfg
        if cfg.moe is not None and cfg.moe.first_k_dense:
            k = cfg.moe.first_k_dense
            return [(k, True), (cfg.n_layers - k, False)]
        return [(cfg.n_layers, cfg.moe is None)]

    def _block_specs(self, dense_mlp: bool) -> Dict[str, Any]:
        cfg = self.cfg
        s: Dict[str, Any] = {
            "ln1": norm_spec(cfg, cfg.d_model),
            "attn": attn.attn_specs(cfg),
            "ln2": norm_spec(cfg, cfg.d_model),
            "ffn": moe_or_mlp_specs(cfg, dense_mlp),
        }
        if cfg.family == "hybrid":
            s["ssm"] = ssm_mod.ssm_specs(cfg)
            s["out_norm_attn"] = norm_spec(cfg, cfg.d_model)
            s["out_norm_ssm"] = norm_spec(cfg, cfg.d_model)
        return s

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        s: Dict[str, Any] = {
            "embed": ParamSpec((self.vp, cfg.d_model), ("vocab", "embed"),
                               "embed"),
            "final_norm": norm_spec(cfg, cfg.d_model),
        }
        if cfg.pos_embedding == "learned":
            s["pos_embed"] = ParamSpec((self.max_cache_len, cfg.d_model),
                                       (None, "embed"), "embed")
        if not cfg.tie_embeddings:
            s["lm_head"] = ParamSpec((cfg.d_model, self.vp),
                                     ("embed", "vocab"))
        for gi, (n, dense) in enumerate(self.layer_groups()):
            s[f"g{gi}"] = stack_specs(self._block_specs(dense), n)
        return s

    # ----------------------------------------------------------------- block
    def _window_eff(self, is_global):
        cfg = self.cfg
        if not cfg.window:
            return 0
        if is_global is None:
            return cfg.window
        return jnp.where(is_global, 0, cfg.window).astype(jnp.int32)

    def _train_mask(self, S: int, window_eff):
        qi = jnp.arange(S)[:, None]
        ki = jnp.arange(S)[None, :]
        m = ki <= qi
        if isinstance(window_eff, int):
            if window_eff:
                m &= ki > qi - window_eff
        else:
            m &= (window_eff == 0) | (ki > qi - window_eff)
        return m

    def _mixer(self, p, x, positions, window_eff, dense_mlp: bool,
               is_global):
        """Token mixer: attention (+ parallel SSM for hybrid)."""
        cfg = self.cfg
        h = apply_norm(cfg, p["ln1"], x)
        a = attn.attention(cfg, p["attn"], h, positions, None, causal=True,
                           window_eff=window_eff)
        if cfg.family == "hybrid":
            s = ssm_mod.ssm_forward(cfg, p["ssm"], h)
            a = 0.5 * (apply_norm(cfg, p["out_norm_attn"], a)
                       + apply_norm(cfg, p["out_norm_ssm"], s))
        return a

    def _is_moe(self, dense_mlp: bool) -> bool:
        return self.cfg.moe is not None and not dense_mlp

    def _ffn(self, p, x, dense_mlp: bool):
        """-> (out, {name: scalar}): the auxiliary loss, and an expert
        layer's counters (``moe.stat_names``)."""
        cfg = self.cfg
        h = apply_norm(cfg, p["ln2"], x)
        if self._is_moe(dense_mlp):
            return moe_forward(cfg, p["ffn"], h)
        return mlp(cfg, p["ffn"], h), {"aux_loss": jnp.zeros((), jnp.float32)}

    def _block(self, p, x, positions, is_global, dense_mlp: bool):
        we = self._window_eff(is_global)
        x = shard_residual(x)
        # constrain the projection outputs themselves so the partitioner
        # reduce-scatters partial sums into the SP layout (half the wire of
        # all-reduce + slice)
        x = x + shard_residual(
            self._mixer(p, x, positions, we, dense_mlp, is_global))
        x = shard_residual(x)
        f, stats = self._ffn(p, x, dense_mlp)
        return shard_residual(x + shard_residual(f)), stats

    def _scan_group(self, gparams, x, positions, flags, dense_mlp: bool,
                    n_layers: int):
        """Run one layer group under scan + remat; returns (x, stats): the
        group's sums of what each layer's ``_ffn`` returns beside its
        output."""
        block = self._block

        def body(carry, xs):
            x, stats = carry
            lp, is_g = xs
            x, s = block(lp, x, positions, is_g, dense_mlp)
            return (x, {k: v + s[k] for k, v in stats.items()}), None

        body = jax.checkpoint(body, policy=REMAT_POLICIES[self.remat],
                              prevent_cse=False)
        names = (stat_names(self.cfg) if self._is_moe(dense_mlp)
                 else ("aux_loss",))
        stats = {k: jnp.zeros((), jnp.float32) for k in names}
        if self.scan_layers:
            (x, stats), _ = jax.lax.scan(body, (x, stats), (gparams, flags))
        else:
            for i in range(n_layers):
                (x, stats), _ = body((x, stats),
                                     (tree_get(gparams, i), flags[i]))
        return x, stats

    # --------------------------------------------------------------- forward
    def _embed(self, params, tokens, pos_offset=0):
        cfg = self.cfg
        x = take_embedding(params["embed"], tokens).astype(
            jnp.dtype(cfg.compute_dtype))
        if cfg.pos_embedding == "learned":
            S = tokens.shape[1]
            pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"],
                                              pos_offset, S, 0)
            x = x + pe.astype(x.dtype)
        return shard_residual(x)

    def _global_flags(self, lo: int, hi: int):
        cfg = self.cfg
        return jnp.array([i in cfg.global_attn_layers
                          for i in range(lo, hi)], bool)

    def _run_layers(self, params, x, positions):
        stats = {}
        lo = 0
        for gi, (n, dense) in enumerate(self.layer_groups()):
            flags = self._global_flags(lo, lo + n)
            x, s = self._scan_group(params[f"g{gi}"], x, positions, flags,
                                    dense, n)
            for k, v in s.items():
                stats[k] = stats.get(k, 0.0) + v
            lo += n
        return x, stats

    def _logits(self, params, x):
        cfg = self.cfg
        x = apply_norm(cfg, params["final_norm"], x)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(x.dtype)
        logits = x @ head
        logits = softcap(logits, cfg.logit_softcap)
        if self.vp != cfg.vocab_size:                 # mask padded vocab rows
            pad_mask = jnp.arange(self.vp) < cfg.vocab_size
            logits = jnp.where(pad_mask, logits, -1e30)
        return logits

    def _forward(self, params, batch):
        """-> (logits, stats): the layers' summed ``_ffn`` scalars."""
        tokens = batch["tokens"]
        S = tokens.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        x = self._embed(params, tokens)
        x, stats = self._run_layers(params, x, positions)
        return self._logits(params, x), stats

    def forward(self, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        logits, stats = self._forward(params, batch)
        return logits, stats["aux_loss"]

    def loss(self, params, batch):
        """Cross-entropy (+ z-loss) + auxiliary loss; the metrics carry the
        expert layers' counters too."""
        logits, stats = self._forward(params, batch)
        loss, metrics = cross_entropy_loss(
            logits, batch["labels"],
            z_loss_weight=getattr(self, "z_loss_weight", 1e-4))
        metrics.update(stats)
        return loss + stats["aux_loss"], metrics

    # ---------------------------------------------------------------- decode
    @property
    def _ring(self) -> bool:
        return bool(self.cfg.window) and self.max_cache_len > self.cfg.window

    @property
    def cache_window(self) -> int:
        return (min(self.cfg.window, self.max_cache_len) if self._ring
                else self.max_cache_len)

    def init_cache(self, batch: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
        cfg = self.cfg
        W = self.cache_window
        cache: Dict[str, Any] = {
            "k": jnp.zeros((cfg.n_layers, batch, W, cfg.n_kv_heads,
                            cfg.qk_head_dim), dtype),
            "v": jnp.zeros((cfg.n_layers, batch, W, cfg.n_kv_heads,
                            cfg.v_head_dim), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
        if cfg.family == "hybrid":
            st = ssm_mod.ssm_init_state(cfg, batch)
            cache["ssm"] = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), st)
        return cache

    def cache_axes(self):
        """Logical sharding axes mirroring init_cache's tree."""
        cfg = self.cfg
        axes = {
            "k": ("layers", "act_batch", "window", "kv_heads", None),
            "v": ("layers", "act_batch", "window", "kv_heads", None),
            "pos": (),
        }
        if cfg.family == "hybrid":
            axes["ssm"] = {
                "conv": ("layers", "act_batch", None, "ffn"),
                "h": ("layers", "act_batch", "ffn", None),
            }
        return axes

    def _stacked_layer_params(self, params):
        """View of all layers' params stacked along axis 0 (concat groups)."""
        groups = [params[f"g{gi}"]
                  for gi in range(len(self.layer_groups()))]
        if len(groups) == 1:
            return groups[0]
        # groups differ in ffn structure; decode handles them separately
        return groups

    def prefill(self, params, batch, cache=None):
        """Forward + cache population.  tokens: (B, S)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        if cache is None:
            cache = self.init_cache(B)
        W = self.cache_window
        positions = jnp.arange(S, dtype=jnp.int32)
        x = self._embed(params, tokens)

        k_all: List[jnp.ndarray] = []
        v_all: List[jnp.ndarray] = []
        ssm_states: List[Any] = []
        aux = jnp.zeros((), jnp.float32)
        lo = 0
        for gi, (n, dense) in enumerate(self.layer_groups()):
            gparams = params[f"g{gi}"]
            flags = self._global_flags(lo, lo + n)

            def body(carry, xs, dense=dense):
                x, aux = carry
                lp, is_g = xs
                h = apply_norm(cfg, lp["ln1"], x)
                q = attn.project_q(cfg, lp["attn"], h, positions)
                k, v = attn.project_kv(cfg, lp["attn"], h, positions)
                a = attn.sdpa_auto(q, k, v, causal=True,
                                   window_eff=self._window_eff(is_g),
                                   scale=attn.softmax_scale(cfg))
                a = a.reshape(B, S, -1) @ lp["attn"]["wo"].astype(x.dtype)
                ys = {"k": k, "v": v}
                if cfg.family == "hybrid":
                    s_out, s_state = ssm_prefill(cfg, lp["ssm"], h)
                    a = 0.5 * (apply_norm(cfg, lp["out_norm_attn"], a)
                               + apply_norm(cfg, lp["out_norm_ssm"], s_out))
                    ys["ssm"] = s_state
                x = x + a
                f, s = self._ffn(lp, x, dense)
                return (x + f, aux + s["aux_loss"]), ys

            body = jax.checkpoint(body, policy=REMAT_POLICIES[self.remat],
                                  prevent_cse=False, static_argnums=())
            (x, aux), ys = jax.lax.scan(body, (x, aux), (gparams, flags))
            k_all.append(ys["k"])
            v_all.append(ys["v"])
            if cfg.family == "hybrid":
                ssm_states.append(ys["ssm"])
            lo += n

        k_full = jnp.concatenate(k_all, 0) if len(k_all) > 1 else k_all[0]
        v_full = jnp.concatenate(v_all, 0) if len(v_all) > 1 else v_all[0]
        # write into (ring) cache: slot s holds the latest position p≡s (mod W)
        if S >= W:
            slot_pos = jnp.array([S - 1 - ((S - 1 - s) % W) for s in range(W)],
                                 jnp.int32)
            k_c = jnp.take(k_full, slot_pos, axis=2)
            v_c = jnp.take(v_full, slot_pos, axis=2)
        else:
            padw = ((0, 0), (0, 0), (0, W - S), (0, 0), (0, 0))
            k_c, v_c = jnp.pad(k_full, padw), jnp.pad(v_full, padw)
        cache = dict(cache)
        cache["k"] = k_c.astype(cache["k"].dtype)
        cache["v"] = v_c.astype(cache["v"].dtype)
        cache["pos"] = jnp.asarray(S, jnp.int32)
        if cfg.family == "hybrid":
            cache["ssm"] = (jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, 0), *ssm_states)
                if len(ssm_states) > 1 else ssm_states[0])
        return self._logits(params, x[:, -1:]), cache

    def decode_step(self, params, tokens, cache):
        """tokens: (B, 1).  Returns (logits (B,1,V), cache)."""
        cfg = self.cfg
        B = tokens.shape[0]
        pos = cache["pos"]
        x = self._embed_decode(params, tokens, pos)
        ring = self._ring

        lo = 0
        new_k, new_v, new_ssm = [], [], []
        for gi, (n, dense) in enumerate(self.layer_groups()):
            gparams = params[f"g{gi}"]
            flags = self._global_flags(lo, lo + n)
            kc = jax.lax.dynamic_slice_in_dim(cache["k"], lo, n, 0)
            vc = jax.lax.dynamic_slice_in_dim(cache["v"], lo, n, 0)
            xs = [gparams, flags, kc, vc]
            if cfg.family == "hybrid":
                xs.append(jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, lo, n, 0),
                    cache["ssm"]))

            def body(x, xs, dense=dense):
                if cfg.family == "hybrid":
                    lp, is_g, kc, vc, sst = xs
                else:
                    lp, is_g, kc, vc = xs
                    sst = None
                h = apply_norm(cfg, lp["ln1"], x)
                a, kc, vc = attn.decode_attention(
                    cfg, lp["attn"], h, pos, kc, vc, ring=ring,
                    is_global=is_g)
                ys = {"k": kc, "v": vc}
                if cfg.family == "hybrid":
                    s_out, sst = ssm_mod.ssm_decode_step(cfg, lp["ssm"], h, sst)
                    a = 0.5 * (apply_norm(cfg, lp["out_norm_attn"], a)
                               + apply_norm(cfg, lp["out_norm_ssm"], s_out))
                    ys["ssm"] = sst
                x = x + a
                f, _ = self._ffn(lp, x, dense)
                return x + f, ys

            x, ys = jax.lax.scan(body, x, tuple(xs))
            new_k.append(ys["k"])
            new_v.append(ys["v"])
            if cfg.family == "hybrid":
                new_ssm.append(ys["ssm"])
            lo += n

        cache = dict(cache)
        cache["k"] = (jnp.concatenate(new_k, 0) if len(new_k) > 1
                      else new_k[0])
        cache["v"] = (jnp.concatenate(new_v, 0) if len(new_v) > 1
                      else new_v[0])
        if cfg.family == "hybrid":
            cache["ssm"] = (jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, 0), *new_ssm)
                if len(new_ssm) > 1 else new_ssm[0])
        cache["pos"] = pos + 1
        return self._logits(params, x), cache

    def _embed_decode(self, params, tokens, pos):
        cfg = self.cfg
        x = take_embedding(params["embed"], tokens).astype(
            jnp.dtype(cfg.compute_dtype))
        if cfg.pos_embedding == "learned":
            pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos, 1, 0)
            x = x + pe.astype(x.dtype)
        return x


def ssm_prefill(cfg, p, x):
    """SSM forward that also returns the decode state (conv tail + h)."""
    out = ssm_mod.ssm_forward(cfg, p, x)
    # recompute the conv input tail for the decode conv state
    xz = x @ p["in_proj"].astype(x.dtype)
    x_in = jnp.split(xz, 2, axis=-1)[0]
    K = cfg.ssm.d_conv
    tail = x_in[:, -(K - 1):]
    B, t = tail.shape[:2]
    if t < K - 1:
        tail = jnp.pad(tail, ((0, 0), (K - 1 - t, 0), (0, 0)))
    # final h: rerun the last chunk scan cheaply via full scan state
    h = _ssm_final_state(cfg, p, x)
    return out, {"conv": tail.astype(jnp.float32), "h": h}


def _ssm_final_state(cfg, p, x):
    from repro.models.ssm import (CHUNK, _causal_depthwise_conv, _discretize,
                                  _scan_chunk, ssm_dims)
    B, S, _ = x.shape
    di, _ = ssm_dims(cfg)
    xz = x @ p["in_proj"].astype(x.dtype)
    x_in = jnp.split(xz, 2, axis=-1)[0]
    x_c = jax.nn.silu(_causal_depthwise_conv(x_in, p["conv_w"], p["conv_b"]))
    L = min(CHUNK, S)
    n = -(-S // L)
    x_cp = jnp.pad(x_c, ((0, 0), (0, n * L - S), (0, 0)))
    # mask padded steps to identity updates so the final state is exact
    valid = (jnp.arange(n * L) < S).astype(jnp.float32)

    def step(h, inp):
        xc, m = inp
        dA, dBx, _ = _discretize(cfg, p, xc)
        dA = dA * m[None, :, None, None] + (1 - m)[None, :, None, None]
        dBx = dBx * m[None, :, None, None]
        _, h = _scan_chunk(dA, dBx, h)
        return h, None

    xs = (x_cp.reshape(B, n, L, di).transpose(1, 0, 2, 3),
          valid.reshape(n, L))
    h0 = jnp.zeros((B, di, cfg.ssm.d_state), jnp.float32)
    h, _ = jax.lax.scan(step, h0, xs)
    return h
