"""The FLOP count behind ``train.mfu``, against a count made by hand."""
import json

import pytest

from bench.common import BENCH
from bench.flops import forward_flops_per_token, train_flops_per_token


def test_qwen3_4b_2l_by_hand():
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.2l.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "b2s4096_hook.json").read_text())
    # per layer, per token, 2 FLOPs per multiply-add:
    #   q 2560x4096, k and v 2560x1024 each, o 4096x2560
    proj = 2 * (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560)   # 52,428,800
    #   scores and values: 32 heads x 128 over 2048.5 causal keys on average
    attn = 2 * 2 * 4096 * 2048.5                                 # 33,562,624
    #   gate, up and down, 2560x9728 each
    mlp = 3 * 2 * 2560 * 9728                                    # 149,422,080
    head = 2 * 2560 * 151936                                     # 777,912,320
    fwd = 2 * (proj + attn + mlp) + head                         # 1,248,739,328
    assert fwd == 1_248_739_328
    assert forward_flops_per_token(cfg, 4096) == pytest.approx(fwd, rel=1e-12)
    assert train_flops_per_token(cfg, traffic) == pytest.approx(3 * fwd,
                                                                rel=1e-12)


def test_close_to_six_n_per_token():
    """Far from the attention term, 3 x forward is 6 x the matrix
    parameters (the embedding counted once, as the tied head)."""
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.2l.json").read_text())
    n = (2560 * 151936 + 2 * (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560
                              + 3 * 2560 * 9728))
    assert train_flops_per_token(cfg, {"seq_len": 1}) == pytest.approx(
        6 * n, rel=1e-4)


class _Trace:
    def __init__(self, program_s, steps):
        self._s, self.steps = program_s, [(0, 1)] * steps

    def program_s(self, pattern):
        return self._s if "train_step" in pattern else None


def test_mfu_reads_the_device_time_of_the_step():
    """``train.mfu`` is a step's FLOPs over its device time in the jitted
    step: the host's time between steps does not enter it."""
    import bench.run as harness
    from bench.common import load_module
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.2l.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "b2s4096_hook.json").read_text())
    run = harness.Run({}, cfg, traffic, 0, 1.0)
    run.peaks, run.devices = {"bf16_flops_per_s": 1e15}, [0]
    run.trace = _Trace(2.5, 10)                  # 0.25 s a step
    reader = load_module(BENCH / "metrics" / "train.mfu.py", "bench_mfu")
    tokens = traffic["global_batch"] * traffic["seq_len"]
    want = 100 * train_flops_per_token(cfg, traffic) * tokens / 0.25 / 1e15
    assert reader.read(run) == pytest.approx(want, rel=1e-12)
    run.devices = [0, 1]                         # the same time on each chip
    assert reader.read(run) == pytest.approx(want / 2, rel=1e-12)
    run.trace = _Trace(None, 10)
    assert reader.read(run) is None
