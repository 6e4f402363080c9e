"""Architecture registry: ``get_config(arch_id)`` / ``get_reduced_config(arch_id)``.

Every assigned architecture lives in its own module exposing ``CONFIG`` (the exact
published shape) and ``reduced()`` (a tiny same-family config for CPU smoke tests).
"""
from __future__ import annotations

import importlib

from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig, QuantConfig,
                                ShapeConfig, SSMConfig, SHAPES, SHAPES_BY_NAME)

ARCH_IDS = (
    "qwen3-moe-30b-a3b",
    "moonlight-16b-a3b",
    "zamba2-1.2b",
    "rwkv6-3b",
    "smollm-135m",
    "command-r-35b",
    "llama3-405b",
    "tinyllama-1.1b",
    "whisper-small",
    "paligemma-3b",
    # the paper's own evaluation model families
    "llama2-7b",
    "mistral-7b",
)

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; choose from {ARCH_IDS}")
    return importlib.import_module(_MODULES[arch]).CONFIG


def get_reduced_config(arch: str) -> ModelConfig:
    return importlib.import_module(_MODULES[arch]).reduced()


__all__ = ["MLAConfig", "ModelConfig", "MoEConfig", "QuantConfig", "ShapeConfig", "SSMConfig",
           "SHAPES", "SHAPES_BY_NAME", "ARCH_IDS", "get_config", "get_reduced_config"]
