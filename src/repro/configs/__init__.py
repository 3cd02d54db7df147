"""Architecture config registry: ``get_config("<arch-id>")``.

Assigned pool (10 archs), the paper's own LSTM language models, and one
chip's share of Moonlight-16B-A3B (latent attention, held experts).
"""
from __future__ import annotations

from repro.configs.base import (
    INPUT_SHAPES,
    L2SConfig,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    ShapeConfig,
    TrainConfig,
    V_BLK,
    shapes_for,
)

from repro.configs.gemma_2b import CONFIG as _gemma_2b
from repro.configs.phi35_moe import CONFIG as _phi35_moe
from repro.configs.smollm_360m import CONFIG as _smollm_360m
from repro.configs.qwen2_vl_2b import CONFIG as _qwen2_vl_2b
from repro.configs.hubert_xlarge import CONFIG as _hubert_xlarge
from repro.configs.starcoder2_3b import CONFIG as _starcoder2_3b
from repro.configs.zamba2_2p7b import CONFIG as _zamba2_2p7b
from repro.configs.qwen15_110b import CONFIG as _qwen15_110b
from repro.configs.mamba2_1p3b import CONFIG as _mamba2_1p3b
from repro.configs.mixtral_8x7b import CONFIG as _mixtral_8x7b
from repro.configs.moonlight_16b_a3b import CONFIG as _moonlight
from repro.configs.ptb_lstm import PTB_SMALL as _ptb_small, PTB_LARGE as _ptb_large
from repro.configs.nmt_deen import CONFIG as _nmt_deen

REGISTRY = {
    c.name: c
    for c in [
        _gemma_2b,
        _phi35_moe,
        _smollm_360m,
        _qwen2_vl_2b,
        _hubert_xlarge,
        _starcoder2_3b,
        _zamba2_2p7b,
        _qwen15_110b,
        _mamba2_1p3b,
        _mixtral_8x7b,
        _moonlight,
        _ptb_small,
        _ptb_large,
        _nmt_deen,
    ]
}

ASSIGNED_ARCHS = (
    "gemma-2b",
    "phi3.5-moe-42b-a6.6b",
    "smollm-360m",
    "qwen2-vl-2b",
    "hubert-xlarge",
    "starcoder2-3b",
    "zamba2-2.7b",
    "qwen1.5-110b",
    "mamba2-1.3b",
    "mixtral-8x7b",
)


def get_config(name: str) -> ModelConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(REGISTRY)}")
    return REGISTRY[name]


__all__ = [
    "ASSIGNED_ARCHS",
    "INPUT_SHAPES",
    "L2SConfig",
    "ModelConfig",
    "MoEConfig",
    "REGISTRY",
    "SSMConfig",
    "ShapeConfig",
    "TrainConfig",
    "V_BLK",
    "get_config",
    "shapes_for",
]
