"""``--arch <id>`` registry: maps architecture ids to their configs."""
from __future__ import annotations

import importlib
from typing import Dict, Union

from repro.configs.base import (ALL_SHAPES, SHAPES_BY_NAME, ArchConfig,
                                CNNConfig, ShapeConfig)

_MODULES: Dict[str, str] = {
    "minicpm-2b": "repro.configs.minicpm_2b",
    "gemma2-9b": "repro.configs.gemma2_9b",
    "phi4-mini-3.8b": "repro.configs.phi4_mini_3_8b",
    "qwen1.5-4b": "repro.configs.qwen1_5_4b",
    "xlstm-350m": "repro.configs.xlstm_350m",
    "recurrentgemma-9b": "repro.configs.recurrentgemma_9b",
    "whisper-tiny": "repro.configs.whisper_tiny",
    "qwen2-vl-2b": "repro.configs.qwen2_vl_2b",
    "granite-moe-1b-a400m": "repro.configs.granite_moe_1b_a400m",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    # the paper's own CNNs, and ResNet-50
    "lenet": "repro.configs.lenet",
    "alexnet": "repro.configs.alexnet",
    "resnet50": "repro.configs.resnet50",
}

CNN_ARCHS = ("lenet", "alexnet", "resnet50")
LM_ARCHS = tuple(k for k in _MODULES if k not in CNN_ARCHS)
ALL_ARCHS = tuple(_MODULES)


def get_arch(name: str) -> Union[ArchConfig, CNNConfig]:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[name]).CONFIG


def get_shape(name: str) -> ShapeConfig:
    return SHAPES_BY_NAME[name]


def iter_cells(include_skipped: bool = True):
    """Yield every (arch, shape, supported) dry-run cell — 40 total."""
    for arch_name in LM_ARCHS:
        cfg = get_arch(arch_name)
        for shape in ALL_SHAPES:
            yield cfg, shape, cfg.supports(shape)
