"""The latent-attention expert cell's driver at a tiny size on the CPU, and
its per-layer readers on a synthetic trace."""
import json
from types import SimpleNamespace

import jax
import pytest

from bench.common import BENCH, load_json, load_module
from bench.xplane import Trace


def tiny_case():
    """The cell's files at a tiny width: 3 layers (one dense), 4 of 16
    experts held from expert 4, 2 x 16 tokens."""
    cfg = json.loads((BENCH / "configs"
                      / "deepseek-v2-lite.ep8.5l.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
               moe_intermediate_size=32, vocab_size=256,
               num_hidden_layers=3, n_routed_experts=4, first_held_expert=4)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    traffic = json.loads((BENCH / "traffic"
                          / "b4s4096_hook.json").read_text())
    traffic.update(global_batch=2, seq_len=16, head_chunks=2)
    return cfg, traffic


def test_float8_control_is_not_correct_under_the_cells_limits():
    """``control_mla_moe.py`` judges its readings by the cell's own limits;
    at a tiny size (4 x 128 tokens, logits spread as at the published
    widths) the float8 control is out on every seed."""
    ctl = load_module(BENCH / "tests" / "control_mla_moe.py",
                      "bench_control_mla_moe")
    limits = load_json(BENCH / "cells"
                       / "train.deepseek-v2-lite.ep8.5l.json")["limits"]
    cfg, traffic = tiny_case()
    cfg.update(vocab_size=1024, initializer_range=0.125)
    traffic.update(global_batch=4, seq_len=128)
    for seed in (11, 12, 13):
        out = ctl.readings(cfg, traffic, seed, jax.devices()[:1])
        assert not ctl.verdict(out["control"], limits)["correct"], out
    assert ctl.verdict({k: v / 2 for k, v in limits.items()},
                       limits) == {"correct": True, "over": []}


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")


def test_driver_records_counters_and_scopes_and_agrees_with_reference():
    cfg, traffic = tiny_case()
    drv = load_module(BENCH / "drivers" / "train_mla_moe.py",
                      "bench_driver_train_mla_moe")
    d = drv.Driver(cfg, traffic, 2 ** 31 + 7, jax.devices()[:1])
    d.setup()
    rec = [d.step(k) for k in range(2)]
    assert all(not r["failed"] and r["tokens"] == 32 for r in rec)
    assert all(0 < r["moe_rows_held"] <= 32 * 6 for r in rec)
    assert set(rec[0]["scopes"].values()) == {
        "mla", "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
        "moe.shared"}
    checks = {c["name"]: c["value"] for c in d.verify()}
    assert set(checks) == {"loss_rel", "grad1_leaf_rel", "delta3_leaf_rel"}
    assert checks["loss_rel"] < 1e-3


def synthetic_run(records):
    """Two 100 ns steps; device 0 runs an ``mla`` fusion 0-30 and 100-120,
    an expert product 40-60 and a while loop over everything."""
    ops = {0: [("%fusion.1", 0, 30), ("%fusion.1", 100, 120),
               ("%ragged.2", 40, 60), ("%while.3", 0, 200)]}
    trace = Trace(ops, {0: [("jit_train_step(1)", 0, 190)]},
                  [("train", 0, 100), ("train", 100, 200)], "train")
    cfg, traffic = tiny_case()
    return SimpleNamespace(trace=trace, records=records, config=cfg,
                           traffic=traffic, devices=[0],
                           peaks={"bf16_flops_per_s": 1e12})


def test_scope_readers_sum_each_scopes_ops_per_step():
    scopes = {"fusion.1": "mla", "ragged.2": "moe.experts"}
    recs = [{"moe_rows_held": 128.0, "scopes": scopes}] * 2
    run = synthetic_run(recs)
    assert reader("train.mla_ms").read(run) == pytest.approx(25e-6)
    assert reader("train.moe.experts_ms").read(run) == pytest.approx(10e-6)
    assert reader("train.moe.dispatch_ms").read(run) is None
    flops = 18 * 64 * 32 * 128
    assert reader("train.moe.experts_roofline").read(run) == pytest.approx(
        100 * flops / (10e-9 * 1e12))


def test_readers_give_nothing_for_a_program_without_scopes_or_counters():
    run = synthetic_run([{"tokens": 32, "failed": False}] * 2)
    for name in ("train.mla_ms", "train.moe.dispatch_ms",
                 "train.moe.experts_ms", "train.moe.experts_roofline"):
        assert reader(name).read(run) is None
