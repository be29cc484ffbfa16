"""Architecture config registry.

``get_config(arch_id)`` returns the full production :class:`ModelConfig` for an
assigned architecture; ``get_config(arch_id, reduced=True)`` returns the
CPU-smoke-test variant of the same family (2 layers, d_model<=512, <=4 experts).
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401  (re-exported)
    AUDIO, DENSE, ENC_DEC, HYBRID, INPUT_SHAPES, MOE, SHAPES_BY_NAME, SSM, VLM,
    DECODE_32K, LONG_500K, PREFILL_32K, TRAIN_4K,
    InputShape, ModelConfig, get_shape,
)

# arch-id -> module name in this package
_REGISTRY: Dict[str, str] = {
    "delphi-2m": "delphi_2m",
    "delphi-100m": "delphi_100m",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "zamba2-1.2b": "zamba2_1_2b",
    "qwen2.5-32b": "qwen2_5_32b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "mamba2-780m": "mamba2_780m",
    "internvl2-26b": "internvl2_26b",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "deepseek-7b": "deepseek_7b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

# The 10 architectures assigned to this paper (delphi-* are the paper's own).
ASSIGNED_ARCHS: List[str] = [
    "seamless-m4t-large-v2",
    "zamba2-1.2b",
    "qwen2.5-32b",
    "qwen2-moe-a2.7b",
    "mamba2-780m",
    "internvl2-26b",
    "tinyllama-1.1b",
    "h2o-danube-1.8b",
    "olmoe-1b-7b",
    "deepseek-7b",
]

ALL_ARCHS: List[str] = list(_REGISTRY)


def get_config(arch_id: str, *, reduced: bool = False) -> ModelConfig:
    if arch_id not in _REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_REGISTRY)}")
    mod = importlib.import_module(f"repro.configs.{_REGISTRY[arch_id]}")
    cfg: ModelConfig = mod.CONFIG
    return cfg.reduced() if reduced else cfg
