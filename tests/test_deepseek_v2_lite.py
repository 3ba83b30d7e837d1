"""DeepSeek-V2-Lite's latent attention and dropless held-expert layer
against the plain reference (``bench/reference/deepseek_v2.py``), at a tiny
size on seeded weights, in float32."""
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn
from repro.models import build_model, moe
from repro.models.common import yarn_inv_freq

from bench.drivers.train import flat
from bench.drivers.train_mla_moe import model_config
from bench.reference import deepseek_v2 as ref
from bench.reference.qwen3 import _dot, seed_key
from bench.scopes import scope_map

ROOT = Path(__file__).resolve().parents[1]
SEED = 2**31 + 12345


def tiny_cfg(**kw) -> dict:
    """The cell's configuration file at a tiny width: 3 layers (one dense),
    4 of 16 experts held from expert 4, top-6."""
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "deepseek-v2-lite.ep8.5l.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
               moe_intermediate_size=32, vocab_size=256,
               num_hidden_layers=3, n_routed_experts=4, first_held_expert=4)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg.update(kw)
    return cfg


def program(cfg: dict):
    """(float32 model, its params from the seed's reference weights)."""
    mc = model_config(cfg).replace(compute_dtype="float32")
    model = build_model(mc)
    spec = model.param_specs()
    is_spec = lambda x: hasattr(x, "axes")          # noqa: E731
    paths = list(flat(spec, is_spec))
    p = ref.init_params(cfg, seed_key(SEED))
    assert set(p) == set(paths)
    tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(spec, is_leaf=is_spec),
        [p[k] for k in paths])
    return model, tree, p


def layer(p: dict, group: str, i: int) -> dict:
    return {k[len(group) + 1:]: a[i] for k, a in p.items()
            if k.startswith(group + "/")}


def close(a, b, rtol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.max(np.abs(b)), 1e-30)
    assert np.max(np.abs(a - b)) / scale < rtol


def test_mla_layer_output_and_gradients_match_reference():
    cfg = tiny_cfg()
    model, params, p = program(cfg)
    w = layer(p, "g0", 0)
    pw = jax.tree_util.tree_map(lambda a: a[0], params["g0"]["attn"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64), jnp.float32)
    r = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64), jnp.float32)
    S = h.shape[1]
    with jax.default_matmul_precision("highest"):
        def prog(h, pw):
            return attn.attention(model.cfg, pw, h, jnp.arange(S), None,
                                  causal=True)

        def plain(h, w):
            return ref.mla(cfg, _dot("float32"), w, h, block=8)

        close(prog(h, pw), plain(h, w))
        gp = jax.grad(lambda h, q: jnp.sum(prog(h, q) * r), (0, 1))(h, pw)
        gr = jax.grad(lambda h, q: jnp.sum(plain(h, q) * r), (0, 1))(h, w)
    close(gp[0], gr[0])
    for k, g in gp[1].items():
        close(g, gr[1]["attn/" + k])


def test_yarn_frequencies_and_scale_follow_the_formulas():
    """A direct transcription of DeepSeek-V2's YaRN rotary embedding at the
    published rope dims: the ramp's ends, the blend, the scale."""
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "deepseek-v2-lite.ep8.5l.json").read_text())
    dim, base = 64, 10000.0
    factor, L0 = 40.0, 4096

    def corr(rot):
        return dim * math.log(L0 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    want = []
    for i in range(dim // 2):
        f = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / factor * ramp + f * (1 - ramp))
    mc = model_config(cfg)
    close(yarn_inv_freq(dim, base, mc.yarn), want, rtol=1e-6)
    close(ref.yarn_inv_freq(cfg), want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert attn.softmax_scale(mc) == pytest.approx(192 ** -0.5 * m * m)
    assert ref.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)


def test_train_step_loss_and_leaf_gradients_match_reference():
    cfg = tiny_cfg()
    model, params, p = program(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 256, (2, 32)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    train = json.loads((ROOT / "bench" / "traffic"
                        / "b4s4096_hook.json").read_text())
    with jax.default_matmul_precision("highest"):
        (lp, metrics), gp = jax.value_and_grad(model.loss, has_aux=True)(
            params, {"tokens": tokens, "labels": labels})
        lr, gr = jax.value_and_grad(lambda q: ref.loss_fn(
            cfg, train, q, tokens, labels, "float32", 2))(p)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    gp = flat(gp)
    for k, g in gr.items():
        assert float(jnp.linalg.norm(gp[k])) == pytest.approx(
            float(jnp.linalg.norm(g)), rel=1e-4, abs=1e-9), k
        routed = ref.routing(cfg, p, tokens)
    first = cfg["first_held_expert"]
    held = sum(int(((i >= first) & (i < first + 4)).sum()) for i in routed)
    assert float(metrics["moe_rows_held"]) == held > 0


def moe_case(first: int, n: int, E: int = 16, k: int = 6):
    cfg = tiny_cfg(n_routed_experts=n, first_held_expert=first,
                   num_experts_per_tok=k)
    cfg["published"] = dict(cfg["published"], n_routed_experts=E)
    mc = model_config(cfg).replace(compute_dtype="float32")
    return cfg, mc


def moe_weights(E: int = 16, d: int = 64, h: int = 32):
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    n = lambda i, s: 0.1 * jax.random.normal(keys[i], s)   # noqa: E731
    return {"router": n(0, (d, E)), "wg": n(1, (E, d, h)),
            "wu": n(2, (E, d, h)), "wd": n(3, (E, h, d)),
            "shared": {"wg": n(4, (d, 2 * h)), "wu": n(5, (d, 2 * h)),
                       "wd": n(6, (2 * h, d))}}


def share(w: dict, first: int, n: int) -> dict:
    return dict(w, **{k: w[k][first:first + n] for k in ("wg", "wu", "wd")})


def reference_moe(cfg, w, h):
    flat_w = {"ffn/router": w["router"], "ffn/wg": w["wg"], "ffn/wu": w["wu"],
              "ffn/wd": w["wd"]}
    flat_w.update({"ffn/shared/" + k: v for k, v in w["shared"].items()})
    return ref.moe(cfg, _dot("float32"), flat_w, h)[0]


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips of two experts each: their routed parts, with the shared
    experts counted once, add up to the whole layer's output."""
    w = moe_weights()
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 64))
    with jax.default_matmul_precision("highest"):
        cfg_all, mc_all = moe_case(0, 16)
        whole, _ = moe.moe_forward(mc_all, w, h)
        close(whole, reference_moe(cfg_all, w, h))
        shared_out = ref.swiglu(_dot("float32"), h, w["shared"]["wg"],
                                w["shared"]["wu"], w["shared"]["wd"])
        parts = [moe.moe_forward(moe_case(2 * i, 2)[1],
                                 share(w, 2 * i, 2), h)[0] - shared_out
                 for i in range(8)]
    close(sum(parts) + shared_out, whole)


def test_skewed_router_drops_no_assignment():
    """Every token's first choice is expert 0: it gets all 64 tokens, four
    times its even share, and the layer still matches the dense form."""
    cfg, mc = moe_case(0, 4, k=4)
    w = moe_weights()
    w["router"] = w["router"].at[:, 0].set(1.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))) + 0.1
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_forward(mc, share(w, 0, 4), h)
        want = reference_moe(cfg, share(w, 0, 4), h)
    close(out, want)
    probs = jax.nn.softmax(h.reshape(64, 64) @ w["router"], -1)
    idx = np.asarray(jax.lax.top_k(probs, 4)[1])
    assert (idx[:, 0] == 0).all()
    assert float(stats["moe_rows_held"]) == float((idx < 4).sum())
    assert np.bincount(idx[idx < 4], minlength=4)[0] == 64


def undefined_outside_groups(real):
    """``jax.lax.ragged_dot`` whose output rows past the groups, and the
    lhs cotangent's, are NaN: what a grouped-product kernel that writes
    only its groups' rows may leave there."""
    def poison(x, sizes):
        live = jnp.arange(x.shape[0]) < jnp.sum(sizes)
        return jnp.where(live[:, None], x, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, sizes):
        return poison(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return ragged_dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        dl, dr = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)[1](ct)
        return poison(dl, sizes), dr, None

    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


def test_rows_outside_the_groups_reach_neither_output_nor_gradient(
        monkeypatch):
    """The grouped products' rows past their groups may hold anything:
    the layer's output and gradients still match the dense form."""
    cfg, mc = moe_case(0, 4)
    w = share(moe_weights(), 0, 4)
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 64))
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        undefined_outside_groups(jax.lax.ragged_dot))
    with jax.default_matmul_precision("highest"):
        def prog(h, w):
            return jnp.sum(jnp.sin(moe.moe_forward(mc, w, h)[0]))

        def plain(h, w):
            return jnp.sum(jnp.sin(reference_moe(cfg, w, h)))

        close(prog(h, w), plain(h, w))
        gp = jax.grad(prog, (0, 1))(h, w)
        gr = jax.grad(plain, (0, 1))(h, w)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        assert bool(jnp.isfinite(a).all())
        close(a, b, rtol=1e-4)


@pytest.mark.parametrize("kv_heads,chunk,S", [(4, 8, 20), (2, 16, 16)])
def test_sdpa_flash_value_width_differs_from_query_width(kv_heads, chunk, S):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 24))
    k = jax.random.normal(ks[1], (2, S, kv_heads, 24))
    v = jax.random.normal(ks[2], (2, S, kv_heads, 16))
    scale = 0.3
    out = attn.sdpa_flash(q, k, v, causal=True, chunk=chunk, scale=scale)
    kr = jnp.repeat(k, 4 // kv_heads, axis=2)
    vr = jnp.repeat(v, 4 // kv_heads, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", a, vr)
    assert out.shape == (2, S, 4, 16)
    close(out, want, rtol=1e-5)


def test_scope_map_names_each_scope_and_skips_control_flow():
    def f(x, w):
        with jax.named_scope("mla"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("moe.experts"):
            z = jax.lax.scan(lambda c, _: (jnp.sin(c @ w), None), y,
                             None, length=3)[0]
        return jnp.sum(z * x)

    x = jnp.ones((8, 8))
    text = jax.jit(jax.grad(f, (0, 1))).lower(x, x).compile().as_text()
    m = scope_map(text, ("mla", "moe.experts"))
    assert set(m.values()) == {"mla", "moe.experts"}
    for name in m:
        assert f"%{name} = " in text or f"{name} = " in text
    whiles = [line.split("=")[0].strip().lstrip("%").replace("ROOT ", "")
              for line in text.splitlines() if " while(" in line]
    assert whiles and not set(whiles) & set(m)


def test_held_experts_need_dropless_routing():
    _, mc = moe_case(0, 4)
    mc = mc.replace(moe=dataclasses.replace(mc.moe, capacity_factor=1.25))
    with pytest.raises(ValueError, match="dropless"):
        moe.moe_forward(mc, share(moe_weights(), 0, 4),
                        jnp.ones((1, 8, 64)))
