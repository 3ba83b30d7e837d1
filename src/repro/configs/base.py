"""Config system: model/shape/mesh/run dataclasses shared by the whole framework.

Every assigned architecture is a ``ModelConfig`` instance in its own module
under ``repro.configs``; the registry maps ``--arch <id>`` to it.  Shapes are
the four assigned input-shape cells (train_4k / prefill_32k / decode_32k /
long_500k).  ``param_count``/``active_param_count`` feed the roofline's
MODEL_FLOPS = 6·N·D term.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# --------------------------------------------------------------------------- #
# Sub-configs
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block config (routed + optional shared experts).

    ``capacity_factor=None`` routes without a capacity: every assignment
    reaches its expert, whatever the imbalance.  ``held`` (first, count)
    names the routed experts this device holds, as one chip of an
    expert-parallel layer does: the router still scores all ``n_experts``,
    and the layer computes the held experts' part of the result alone
    (dropless routing only; None holds them all).
    """

    n_experts: int
    top_k: int
    d_expert: int                      # per-expert hidden dim
    n_shared: int = 0                  # always-on shared experts
    router: str = "softmax"            # "softmax" | "sigmoid" (deepseek-v3)
    capacity_factor: Optional[float] = 1.25  # padded dispatch capacity (paper: padded GEMMs); None = dropless
    aux_loss_weight: float = 0.01      # load-balancing auxiliary loss
    first_k_dense: int = 0             # leading layers that use a dense MLP
    d_ff_dense: int = 0                # dense-MLP hidden dim for those layers
    norm_topk: bool = True             # renormalise the top-k gates to sum to 1
    held: Optional[Tuple[int, int]] = None   # (first, count) of the held experts

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)

    @property
    def n_held(self) -> int:
        return self.held_range[1]


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values expand
    from one RMS-normed latent of ``kv_lora_rank`` per token, and a rotary
    key part of ``rope_dim`` is shared by every head.  Queries project
    directly (no query low-rank)."""

    kv_lora_rank: int
    nope_dim: int                      # per-head query/key dims without rope
    rope_dim: int                      # per-head query/key dims with rope
    v_dim: int                         # per-head value dim


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling as DeepSeek-V2 applies it: frequencies blended
    between interpolated and original by a ramp over the rotary dims, and
    the softmax scale multiplied by ``mscale(factor, mscale_all_dim) ** 2``."""

    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-style selective SSM config (hymba's parallel SSM heads)."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                   # 0 -> ceil(d_model/16)


@dataclass(frozen=True)
class RWKVConfig:
    """RWKV6 (Finch) time-mix / channel-mix config."""

    head_dim: int = 64
    decay_lora: int = 64               # low-rank dim for data-dependent decay
    mix_lora: int = 32                 # low-rank dim for the 5-way token-shift mix
    ffn_mult: float = 3.5              # channel-mix hidden = ffn_mult * d_model


@dataclass(frozen=True)
class VisionConfig:
    """Stub modality frontend: inputs are precomputed patch embeddings."""

    vision_dim: int = 1280             # dim of precomputed patch embeddings
    vision_seq: int = 1601             # patches per image (stubbed frontend)
    cross_attn_every: int = 5          # every k-th layer is a cross-attn layer


@dataclass(frozen=True)
class AudioConfig:
    """Stub audio frontend: inputs are precomputed mel-frame embeddings."""

    frame_dim: int = 80                # mel bins of precomputed frames
    frame_seq: int = 1500              # encoder positions (whisper: 30 s / 20 ms)


# --------------------------------------------------------------------------- #
# Model config
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                        # dense | moe | encdec | hybrid | vlm | rwkv
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0                    # 0 -> d_model // n_heads
    # --- block options ------------------------------------------------------
    activation: str = "silu"           # silu | squared_relu | gelu
    gated_mlp: bool = True
    qkv_bias: bool = False
    qk_norm: bool = False
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    norm_eps: float = 1e-5
    rope_theta: float = 10_000.0
    pos_embedding: str = "rope"        # rope | learned | none
    tie_embeddings: bool = False
    attention: str = "full"            # full | sliding | none
    window: int = 0                    # sliding-window size
    global_attn_layers: tuple = ()     # layers forced to full attention (hymba)
    logit_softcap: float = 0.0         # grok-style tanh soft-capping (0 = off)
    max_seq_len: int = 131_072
    # --- family extensions ---------------------------------------------------
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None    # latent attention in place of q/k/v
    yarn: Optional[YarnConfig] = None  # rotary scaling (MLA's rope dims)
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    vision: Optional[VisionConfig] = None
    audio: Optional[AudioConfig] = None
    enc_layers: int = 0                # encoder depth for enc-dec (whisper)
    # --- numerics ------------------------------------------------------------
    param_dtype: str = "float32"       # master/param dtype for training
    compute_dtype: str = "bfloat16"    # activation/matmul dtype
    serve_dtype: str = "bfloat16"      # weight dtype for inference
    # --- provenance ----------------------------------------------------------
    source: str = ""                   # [source; verified-tier] from assignment

    # ------------------------------------------------------------------ utils
    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head else self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def qk_head_dim(self) -> int:
        """Per-head width of queries and keys."""
        if self.mla is not None:
            return self.mla.nope_dim + self.mla.rope_dim
        return self.head_dim

    @property
    def v_head_dim(self) -> int:
        return self.mla.v_dim if self.mla is not None else self.head_dim

    @property
    def is_subquadratic(self) -> bool:
        """Whether long-context decode (long_500k) is feasible."""
        if self.family in ("rwkv",):
            return True
        if self.family == "hybrid":
            return self.attention == "sliding"
        return self.attention == "sliding"

    @property
    def has_decoder(self) -> bool:
        return True  # every assigned arch has a decode path (whisper is enc-dec)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------- param accounting
    def _mlp_params(self, d_ff: int) -> int:
        mats = 3 if self.gated_mlp else 2
        return mats * self.d_model * d_ff

    def _attn_params(self) -> int:
        if self.mla is not None:
            m, H, d = self.mla, self.n_heads, self.d_model
            return (d * H * self.qk_head_dim                      # Wq
                    + d * (m.kv_lora_rank + m.rope_dim)           # Wkv_a
                    + m.kv_lora_rank * H * (m.nope_dim + m.v_dim)  # Wkv_b
                    + H * m.v_dim * d)                            # Wo
        p = self.d_model * self.q_dim            # Wq
        p += 2 * self.d_model * self.kv_dim      # Wk, Wv
        p += self.q_dim * self.d_model           # Wo
        if self.qkv_bias:
            p += self.q_dim + 2 * self.kv_dim
        return p

    def _ssm_params(self) -> int:
        if self.ssm is None:
            return 0
        c = self.ssm
        d_inner = c.expand * self.d_model
        dt_rank = c.dt_rank or -(-self.d_model // 16)
        p = self.d_model * 2 * d_inner           # in_proj (x, z)
        p += d_inner * c.d_conv                  # depthwise conv
        p += d_inner * (dt_rank + 2 * c.d_state) # x -> (dt, B, C)
        p += dt_rank * d_inner                   # dt proj
        p += d_inner * c.d_state                 # A_log
        p += d_inner                             # D
        p += d_inner * self.d_model              # out proj
        return p

    def _rwkv_layer_params(self) -> int:
        c = self.rwkv
        d = self.d_model
        # time-mix: r,k,v,g,o projections + low-rank decay + low-rank mix + ln_x
        p = 5 * d * d
        p += 2 * d * c.decay_lora
        p += 5 * 2 * d * c.mix_lora              # 5-way token-shift mix LoRA
        p += 2 * d                               # per-head group-norm (ln_x)
        p += 2 * (d // c.head_dim) * c.head_dim  # time_first/time_decay bases
        # channel-mix: k (d->h), v (h->d), r (d->d)
        h = int(c.ffn_mult * d)
        p += d * h + h * d + d * d
        return p

    def layer_params(self, layer_idx: int) -> int:
        """Parameter count of one decoder layer (norms excluded: negligible)."""
        if self.family == "rwkv":
            return self._rwkv_layer_params()
        p = self._attn_params()
        if self.family == "hybrid":
            p += self._ssm_params()
        if self.family == "vlm" and self.vision is not None:
            k = self.vision.cross_attn_every
            if (layer_idx + 1) % k == 0:
                p += self._attn_params()         # extra cross-attn projections
        if self.moe is not None:
            if layer_idx < self.moe.first_k_dense:
                p += self._mlp_params(self.moe.d_ff_dense or self.d_ff)
            else:
                n = self.moe.n_held + self.moe.n_shared
                p += n * self._mlp_params(self.moe.d_expert)
                p += self.d_model * self.moe.n_experts   # router
        else:
            p += self._mlp_params(self.d_ff)
        return p

    def active_layer_params(self, layer_idx: int) -> int:
        """Params touched per token (MoE: shared + top_k experts only)."""
        if self.moe is None or layer_idx < (self.moe.first_k_dense or 0):
            return self.layer_params(layer_idx)
        p = self._attn_params()
        if self.family == "hybrid":
            p += self._ssm_params()
        k = self.moe.top_k + self.moe.n_shared
        p += k * self._mlp_params(self.moe.d_expert)
        p += self.d_model * self.moe.n_experts
        return p

    def param_count(self) -> int:
        p = sum(self.layer_params(i) for i in range(self.n_layers))
        emb = self.vocab_size * self.d_model
        p += emb if self.tie_embeddings else 2 * emb
        if self.pos_embedding == "learned":
            p += self.max_seq_len * self.d_model
        if self.enc_layers:                      # whisper encoder (dense MHA+MLP)
            enc = self.enc_layers * (4 * self.d_model * self.d_model
                                     + 2 * self.d_model * self.d_ff)
            dec_cross = self.n_layers * self._attn_params()  # decoder cross-attn
            p += enc + dec_cross
        if self.vision is not None:
            p += self.vision.vision_dim * self.d_model      # connector proj
        if self.audio is not None:
            p += self.audio.frame_dim * self.d_model        # conv-stub proj
        return p

    def active_param_count(self) -> int:
        p = sum(self.active_layer_params(i) for i in range(self.n_layers))
        emb = self.vocab_size * self.d_model
        p += emb if self.tie_embeddings else 2 * emb
        if self.enc_layers:
            p += self.enc_layers * (4 * self.d_model * self.d_model
                                    + 2 * self.d_model * self.d_ff)
            p += self.n_layers * self._attn_params()
        return p


# --------------------------------------------------------------------------- #
# Input shapes (assigned cells)
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                          # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    "train",   4_096,   256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768,  32),
    "decode_32k":  ShapeConfig("decode_32k",  "decode",  32_768,  128),
    "long_500k":   ShapeConfig("long_500k",   "decode",  524_288, 1),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> bool:
    """Spec: long_500k needs sub-quadratic attention; decode needs a decoder."""
    if shape.name == "long_500k":
        return model.is_subquadratic
    if shape.kind == "decode":
        return model.has_decoder
    return True


# --------------------------------------------------------------------------- #
# Run / parallelism config
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ParallelConfig:
    multi_pod: bool = False
    fsdp_over_pod: Optional[bool] = None   # None -> auto (>=30B params)
    sequence_parallel: bool = True         # SP residual-stream sharding
    remat_policy: str = "nothing"          # nothing | dots | full
    scan_layers: bool = True
    explicit_overlap: bool = False         # shard_map prefetch FSDP variant
    grad_compression: str = "none"         # none | int8 (pod-axis RS)

    def fsdp_axes(self, model: ModelConfig) -> tuple:
        over_pod = self.fsdp_over_pod
        if over_pod is None:
            over_pod = model.param_count() >= 30e9
        if self.multi_pod and over_pod:
            return ("pod", "data")
        return ("data",)

    def batch_axes(self) -> tuple:
        return ("pod", "data") if self.multi_pod else ("data",)


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    min_lr_frac: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    z_loss_weight: float = 1e-4
    seed: int = 0
    checkpoint_every: int = 200
    checkpoint_dir: str = "checkpoints"
    keep_checkpoints: int = 3


# --------------------------------------------------------------------------- #
# Reduced (smoke) configs
# --------------------------------------------------------------------------- #
def reduce_config(cfg: ModelConfig) -> ModelConfig:
    """Shrink a config to laptop scale, preserving family features.

    Used by per-arch smoke tests: same block structure (MoE routing, ssm,
    cross-attn interleave, enc-dec, qk-norm, ...) at tiny dims.
    """
    kw: dict = dict(
        n_layers=min(cfg.n_layers, 4),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        d_head=16,
        d_ff=128,
        vocab_size=512,
        max_seq_len=256,
        window=min(cfg.window, 32) if cfg.window else 0,
    )
    if cfg.global_attn_layers:
        kw["global_attn_layers"] = (0, kw["n_layers"] - 1)
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            n_experts=4,
            top_k=min(cfg.moe.top_k, 2),
            n_shared=min(cfg.moe.n_shared, 1),
            d_expert=32,
            first_k_dense=min(cfg.moe.first_k_dense, 1),
            d_ff_dense=128 if cfg.moe.first_k_dense else 0,
        )
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(cfg.mla, kv_lora_rank=32, nope_dim=16,
                                        rope_dim=8, v_dim=16)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=8)
    if cfg.rwkv is not None:
        kw["rwkv"] = dataclasses.replace(cfg.rwkv, head_dim=16, decay_lora=8,
                                         mix_lora=8)
    if cfg.vision is not None:
        kw["vision"] = dataclasses.replace(cfg.vision, vision_dim=32,
                                           vision_seq=16, cross_attn_every=2)
        kw["n_layers"] = 4
    if cfg.audio is not None:
        kw["audio"] = dataclasses.replace(cfg.audio, frame_dim=16, frame_seq=32)
    if cfg.enc_layers:
        kw["enc_layers"] = 2
    return cfg.replace(name=cfg.name + "-reduced", **kw)


SMOKE_SHAPE = ShapeConfig("smoke", "train", 32, 4)
