"""Architecture registry: ``--arch <id>`` resolves here."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (BlockSpec, ModelConfig, MoEConfig, YarnConfig,
                                reduced)
from repro.configs.shapes import (SHAPES, SHAPES_BY_NAME, InputShape,
                                  get_shape, smoke_shape)

# arch-id -> module path (one module per assigned architecture)
_ARCH_MODULES: Dict[str, str] = {
    "dbrx-132b": "repro.configs.dbrx_132b",
    "starcoder2-3b": "repro.configs.starcoder2_3b",
    "musicgen-large": "repro.configs.musicgen_large",
    "minitron-8b": "repro.configs.minitron_8b",
    "starcoder2-7b": "repro.configs.starcoder2_7b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "xlstm-350m": "repro.configs.xlstm_350m",
    "recurrentgemma-9b": "repro.configs.recurrentgemma_9b",
    "llava-next-mistral-7b": "repro.configs.llava_next_mistral_7b",
    "qwen2-1.5b": "repro.configs.qwen2_1_5b",
    "mellum2-12b-a2.5b": "repro.configs.mellum2_12b",
    "mellum2-12b-a2.5b-d12": "repro.configs.mellum2_12b_d12",
}

ARCH_IDS: List[str] = list(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; options: {ARCH_IDS}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


__all__ = [
    "ARCH_IDS", "BlockSpec", "InputShape", "ModelConfig", "MoEConfig",
    "SHAPES", "SHAPES_BY_NAME", "YarnConfig", "all_configs", "get_config",
    "get_shape", "reduced", "smoke_shape",
]
