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
    traffic = json.loads((BENCH / "traffic" / "b4s512_hook.json").read_text())
    traffic.update(global_batch=batch, seq_len=seq, head_chunks=2)
    return cfg, traffic
