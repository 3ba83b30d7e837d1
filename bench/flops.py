"""Operations a decoder-only transformer's train step needs, from its shapes.

The count is of the work the model requires, whatever computes it: matrix
products only (norms, rotary embedding, softmax and the optimizer are
left out as small), causal attention over ``(S + 1) / 2`` keys per token on
average, the tied LM head counted once in the forward pass, and the
backward pass as twice the forward.  Forward work recomputed under
rematerialisation is not counted.
"""
from __future__ import annotations


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    d, dff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    qd = cfg["num_attention_heads"] * hd
    kvd = cfg["num_key_value_heads"] * hd
    proj = 2 * d * (qd + 2 * kvd) + 2 * qd * d
    attn = 2 * 2 * qd * (seq + 1) / 2          # scores and values
    mlp = 3 * 2 * d * dff                      # gate, up, down
    head = 2 * d * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (proj + attn + mlp) + head


def train_flops_per_token(cfg: dict, traffic: dict) -> float:
    return 3.0 * forward_flops_per_token(cfg, traffic["seq_len"])
