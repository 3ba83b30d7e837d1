"""deepseek-v2-lite — DeepSeek-V2-Lite (15.7B total, 2.4B active): 27
layers of multi-head latent attention (16 heads, KV latent 512, no query
low-rank, YaRN rope), a dense first layer and 26 layers of 64 routed
experts (top-6 by softmax, no renormalisation) beside 2 shared experts;
untied 102,400-row head.  [HF deepseek-ai/DeepSeek-V2-Lite config.json;
arXiv:2405.04434]"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=128,
    d_ff=10944,
    vocab_size=102400,
    activation="silu",
    gated_mlp=True,
    norm_eps=1e-6,
    rope_theta=10_000.0,
    max_seq_len=163840,
    mla=MLAConfig(kv_lora_rank=512, nope_dim=128, rope_dim=64, v_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_len=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
                  router="softmax", capacity_factor=None,
                  aux_loss_weight=0.001, first_k_dense=1, d_ff_dense=10944,
                  norm_topk=False),
    source="[HF deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]",
)
