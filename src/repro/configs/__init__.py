"""Architecture registry: ``--arch <id>`` resolves here."""
from __future__ import annotations

from .base import (AttentionSpec, ByzantineConfig, InputShape, ModelConfig,
                   MoESpec, RecoveryConfig, RWKVSpec, SSMSpec, TrainConfig,
                   YarnSpec)
from .shapes import SHAPES, get_shape

from . import (dbrx_132b, deepseek_v2_236b, deepseek_v2_lite, minicpm3_4b,
               musicgen_large, nemotron_4_15b, phi_3_vision_4_2b, qwen3_0_6b,
               qwen3_1_7b, rwkv6_7b, zamba2_2_7b)

ARCHS = {
    "deepseek-v2-236b": deepseek_v2_236b.CONFIG,
    "deepseek-v2-lite": deepseek_v2_lite.CONFIG,
    "phi-3-vision-4.2b": phi_3_vision_4_2b.CONFIG,
    "nemotron-4-15b": nemotron_4_15b.CONFIG,
    "musicgen-large": musicgen_large.CONFIG,
    "minicpm3-4b": minicpm3_4b.CONFIG,
    "dbrx-132b": dbrx_132b.CONFIG,
    "zamba2-2.7b": zamba2_2_7b.CONFIG,
    "qwen3-0.6b": qwen3_0_6b.CONFIG,
    "qwen3-1.7b": qwen3_1_7b.CONFIG,
    "rwkv6-7b": rwkv6_7b.CONFIG,
}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


__all__ = [
    "ARCHS", "get_config", "get_shape", "SHAPES",
    "AttentionSpec", "ByzantineConfig", "InputShape", "ModelConfig",
    "MoESpec", "RWKVSpec", "SSMSpec", "TrainConfig", "YarnSpec",
]
