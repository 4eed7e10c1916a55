"""Architecture & shape registry.

Every assigned architecture registers its exact published config here (one
file per arch, dims pinned from the assignment table) plus a *reduced* smoke
config of the same family for CPU tests.  Shapes are the assigned input
shapes; ``applicable`` encodes the assignment's skip rules (long_500k needs
sub-quadratic context).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

from repro.models.config import ModelConfig

__all__ = ["ShapeSpec", "SHAPES", "register", "get_config", "get_smoke_config",
           "list_archs", "cells", "ArchEntry"]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ArchEntry:
    config: ModelConfig
    smoke: ModelConfig
    source: str          # provenance tag from the assignment table


_REGISTRY: Dict[str, ArchEntry] = {}


def register(config: ModelConfig, smoke: ModelConfig, source: str) -> None:
    _REGISTRY[config.name] = ArchEntry(config, smoke, source)


def _ensure_loaded() -> None:
    # import all arch modules exactly once (registration side effect)
    from repro.configs import (  # noqa: F401
        grok_1_314b, qwen3_moe_235b_a22b, rwkv6_3b, qwen2_5_3b, minicpm_2b,
        qwen3_32b, phi3_mini_3_8b, musicgen_large, zamba2_2_7b, qwen2_vl_7b,
        deepseek_v3,
    )


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {list_archs()}")
    return _REGISTRY[name].config


def get_smoke_config(name: str) -> ModelConfig:
    _ensure_loaded()
    return _REGISTRY[name].smoke


def list_archs() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def applicable(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Assignment rules: long_500k only for sub-quadratic context archs."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("SKIP: pure full-attention arch — 512k-token context "
                       "requires sub-quadratic attention (assignment rule; "
                       "see DESIGN.md §Arch-applicability)")
    return True, ""


def cells() -> List[Tuple[str, str, bool, str]]:
    """All 40 (arch, shape) cells with applicability flags."""
    _ensure_loaded()
    out = []
    for arch in list_archs():
        cfg = _REGISTRY[arch].config
        for sname, spec in SHAPES.items():
            ok, why = applicable(cfg, spec)
            out.append((arch, sname, ok, why))
    return out
