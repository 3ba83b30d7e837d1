"""Model FLOP utilisation of the whole train step: the forward and
backward FLOPs each token needs (``bench/flops.py``, from the
configuration's shapes; recomputation not counted), times the tokens per
second of the whole window, over chips x the bf16 peak of
``bench/peaks.json``."""
from bench.flops import train_flops_per_token


def read(run):
    tokens = sum(r["tokens"] for r in run.records)
    rate = tokens / run.window_s
    peak = run.peaks["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * train_flops_per_token(run.config, run.traffic) * rate / peak
