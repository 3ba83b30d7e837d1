"""The expert products' share of their roofline: the operations of the rows
they compute (``moe_rows_held`` of each traced step, the assignments to
held experts, times a row's gated MLP forward and backward,
``bench/flops_mla_moe.py``) over ``train.moe.experts_ms``'s device time x chips x the bf16 peak of
``bench/peaks.json``.  Compute bounds them, so the operations set the
least time: at ~1,500 rows an expert a step, each product does over 500
operations per byte it moves, above the v5e's ~240 per byte of bandwidth."""
from bench.flops_mla_moe import expert_flops_per_row
from bench.scopes import scope_ms, traced_records


def read(run):
    ms = scope_ms(run, ("moe.experts",))
    rows = [r["moe_rows_held"] for r in traced_records(run)
            if "moe_rows_held" in r]
    if ms is None or not rows:
        return None
    flops = expert_flops_per_row(run.config) * sum(rows) / len(rows)
    peak = run.peaks["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * flops / (ms / 1e3 * peak)
