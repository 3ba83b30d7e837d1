"""Architecture registry: ``--arch <id>`` -> ModelConfig.

The 10 assigned architectures, the paper's own three workloads and the
latent-attention MoE the trainer benchmarks (deepseek-v2-lite).  IDs match
the assignment exactly (dots and dashes); module names are sanitized.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (SHAPES, ModelConfig, ShapeConfig,
                                reduce_config, shape_applicable)

# arch-id -> module (under repro.configs)
_ARCH_MODULES: Dict[str, str] = {
    # --- assigned pool (10) -------------------------------------------------
    "grok-1-314b":          "grok_1_314b",
    "deepseek-moe-16b":     "deepseek_moe_16b",
    "whisper-medium":       "whisper_medium",
    "nemotron-4-15b":       "nemotron_4_15b",
    "qwen2.5-32b":          "qwen2_5_32b",
    "qwen3-4b":             "qwen3_4b",
    "deepseek-7b":          "deepseek_7b",
    "hymba-1.5b":           "hymba_1_5b",
    "llama-3.2-vision-90b": "llama_3_2_vision_90b",
    "rwkv6-3b":             "rwkv6_3b",
    # --- paper's own workloads (Table II, §VII-C) ----------------------------
    "llama3.1-8b":          "llama3_1_8b",
    "mistral-7b":           "mistral_7b",
    "deepseek-v3-16b":      "deepseek_v3_16b",
    "deepseek-v2-lite":     "deepseek_v2_lite",
}

ASSIGNED_ARCHS: List[str] = list(_ARCH_MODULES)[:10]
PAPER_ARCHS: List[str] = list(_ARCH_MODULES)[10:]


def list_archs(include_paper: bool = True) -> List[str]:
    return list(_ARCH_MODULES) if include_paper else list(ASSIGNED_ARCHS)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(
            f"unknown arch {arch!r}; available: {', '.join(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")
    return mod.CONFIG


def get_reduced_config(arch: str) -> ModelConfig:
    return reduce_config(get_config(arch))


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def iter_cells(include_paper: bool = False):
    """Yield every applicable (arch, shape) dry-run cell (+ skip records)."""
    for arch in list_archs(include_paper):
        cfg = get_config(arch)
        for shape in SHAPES.values():
            yield arch, shape.name, shape_applicable(cfg, shape)
