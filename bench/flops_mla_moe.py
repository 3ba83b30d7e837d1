"""Operations a DeepSeek-V2 train step needs on one chip's share of an
expert-parallel layer, from its configuration's shapes.

The count is of the work the model requires, whatever computes it: matrix
products only (norms, rotary embedding, softmax, routing's sort and the
optimizer are left out as small).  Per token: the latent attention's
projections (queries, the joint latent and rotary key, the latent's
expansion to keys and values, the output); causal attention over
``(S + 1) / 2`` keys on average, scores over the query/key width and values
over the value width; the dense layers' gated MLP; in each expert layer
the router over every published expert, the shared experts, and the held
experts' share of the routed rows, ``k * E_held / E`` rows a token; the
sliced, untied head.  The backward pass counts twice the forward; forward
work recomputed under rematerialisation is not counted.
"""
from __future__ import annotations


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    h = cfg["moe_intermediate_size"]
    routers = cfg["published"]["n_routed_experts"]
    proj = (2 * d * H * (nope + rope) + 2 * d * (r + rope)
            + 2 * r * H * (nope + vd) + 2 * H * vd * d)
    attn = 2 * H * (nope + rope + vd) * (seq + 1) / 2
    dense = 3 * 2 * d * cfg["intermediate_size"]
    held_rows = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                 / routers)
    moe = (2 * d * routers + 3 * 2 * d * h * cfg["n_shared_experts"]
           + held_rows * 3 * 2 * d * h)
    k = cfg["first_k_dense_replace"]
    L = cfg["num_hidden_layers"]
    head = 2 * d * cfg["vocab_size"]
    return L * (proj + attn) + k * dense + (L - k) * moe + head


def train_flops_per_token(cfg: dict, traffic: dict) -> float:
    return 3.0 * forward_flops_per_token(cfg, traffic["seq_len"])


def expert_flops_per_row(cfg: dict) -> float:
    """A routed row's gated MLP of one expert, forward and backward."""
    return 3.0 * 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
