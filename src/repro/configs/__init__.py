"""Assigned architecture configs (10).

Each module exposes ``CONFIG`` (exact pool spec) — retrieve via
``get_config(name)``.
"""
from __future__ import annotations

import importlib

from ..models.config import ModelConfig

ARCH_IDS = [
    "qwen2_1_5b",
    "gemma2_27b",
    "gemma3_12b",
    "phi4_mini_3_8b",
    "deepseek_v2_lite_16b",
    "deepseek_v3_671b",
    "qwen2_vl_2b",
    "whisper_small",
    "xlstm_350m",
    "recurrentgemma_2b",
]

def get_config(name: str) -> ModelConfig:
    key = name.replace("-", "_").replace(".", "_")
    if key not in ARCH_IDS:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f".{key}", __package__)
    return mod.CONFIG
