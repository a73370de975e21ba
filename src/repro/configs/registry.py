"""Architecture registry: ``--arch <id>`` -> (CONFIG, SMOKE).

The 10 assigned archs form the 40-cell dry-run matrix. The extras are the
Whisper ladder beyond tiny: base and small (the paper's own scaling study)
and large-v3 (Whisper's flagship). All are served by the same engine and
scheduler; small and large-v3 have on-chip benchmark cells (``chip_bench/``).
"""
from __future__ import annotations

import importlib
from typing import Dict, Tuple

from repro.configs.base import (
    ALL_SHAPES, SHAPES_BY_NAME, ModelConfig, ShapeConfig, shape_applicable,
)

# assigned id -> module name
ASSIGNED: Dict[str, str] = {
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "mamba2-780m": "mamba2_780m",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "qwen1.5-110b": "qwen1_5_110b",
    "internlm2-20b": "internlm2_20b",
    "qwen2.5-14b": "qwen2_5_14b",
    "whisper-tiny": "whisper_tiny",
    "arctic-480b": "arctic_480b",
    "olmoe-1b-7b": "olmoe_1b_7b",
}

EXTRA: Dict[str, str] = {
    "whisper-base": "whisper_base",
    "whisper-small": "whisper_small",
    "whisper-large-v3": "whisper_large_v3",
}

ALL_ARCHS: Dict[str, str] = {**ASSIGNED, **EXTRA}


def _load(module_name: str):
    return importlib.import_module(f"repro.configs.{module_name}")


def get_config(arch: str) -> ModelConfig:
    if arch not in ALL_ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ALL_ARCHS)}")
    return _load(ALL_ARCHS[arch]).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    if arch not in ALL_ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ALL_ARCHS)}")
    return _load(ALL_ARCHS[arch]).SMOKE


def get_shape(name: str) -> ShapeConfig:
    return SHAPES_BY_NAME[name]


def dryrun_cells():
    """Yield every (arch, shape, applicable, reason) cell of the matrix."""
    for arch in ASSIGNED:
        cfg = get_config(arch)
        for shape in ALL_SHAPES:
            ok, reason = shape_applicable(cfg, shape)
            yield arch, shape.name, ok, reason
