"""Tiny configurations of each driver, for the CPU tests."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def sweep_case(samples=3, iterations=12, verify_samples=2):
    cfg = json.loads((BENCH / "configs" / "cluster-dp.2n.json").read_text())
    traffic = {"samples": samples, "iterations": iterations,
               "verify_samples": verify_samples}
    return cfg, traffic


def train_case(layers=2, batch=2, seq=16):
    cfg = json.loads((BENCH / "configs" / "qwen3-4b.2l.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256,
               num_hidden_layers=layers)
    traffic = json.loads((BENCH / "traffic" / "b2s4096_hook.json").read_text())
    traffic.update(global_batch=batch, seq_len=seq, head_chunks=2)
    return cfg, traffic


def train_check_case():
    """A tiny train case in which the float8 control departs from the
    reference about as far as at the cells' own sizes: logits that spread
    as the published widths' do at initialisation (hidden_size x
    initializer_range**2 = 1, as 2560 x 0.02**2 ~ 1.02) over 512 tokens a
    step, enough that rounding averages out of the loss as it does over the
    cell's 8,192."""
    cfg, traffic = train_case(batch=4, seq=128)
    cfg.update(vocab_size=1024, initializer_range=0.125)
    return cfg, traffic
