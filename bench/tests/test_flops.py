"""The FLOP count behind ``train.mfu``, against a count made by hand."""
import json

import pytest

from bench.common import BENCH
from bench.flops import forward_flops_per_token, train_flops_per_token


def test_qwen3_4b_2l_by_hand():
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.2l.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "b4s512_hook.json").read_text())
    # per layer, per token, 2 FLOPs per multiply-add:
    #   q 2560x4096, k and v 2560x1024 each, o 4096x2560
    proj = 2 * (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560)   # 52,428,800
    #   scores and values: 32 heads x 128 over 256.5 causal keys on average
    attn = 2 * 2 * 4096 * 256.5                                  # 4,202,496
    #   gate, up and down, 2560x9728 each
    mlp = 3 * 2 * 2560 * 9728                                    # 149,422,080
    head = 2 * 2560 * 151936                                     # 777,912,320
    fwd = 2 * (proj + attn + mlp) + head                         # 1,190,019,072
    assert fwd == 1_190_019_072
    assert forward_flops_per_token(cfg, 512) == pytest.approx(fwd, rel=1e-12)
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
