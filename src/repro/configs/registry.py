"""Architecture registry: ``get_preset(name, preset)`` -> the arch's SMOKE,
CHIP (where defined) or FULL config; ``get_smoke``/``get_full`` name two.

Every config cites its source (paper / model card) per the assignment pool.
``--arch <id>`` in the launchers resolves through here.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ArchConfig

_ARCHS: Dict[str, str] = {
    "zamba2-7b": "repro.configs.zamba2_7b",
    "granite-8b": "repro.configs.granite_8b",
    "qwen1.5-110b": "repro.configs.qwen15_110b",
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "qwen2.5-32b": "repro.configs.qwen25_32b",
    "musicgen-large": "repro.configs.musicgen_large",
    "qwen1.5-32b": "repro.configs.qwen15_32b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "llama-3.2-vision-11b": "repro.configs.llama32_vision_11b",
    "xlstm-350m": "repro.configs.xlstm_350m",
    # the paper's own experiments (synthetic cosine / financial monitoring)
    "paper-synthetic": "repro.configs.paper_synthetic",
    "paper-financial": "repro.configs.paper_financial",
}


def names(include_paper: bool = False) -> List[str]:
    ns = [n for n in _ARCHS if not n.startswith("paper-")]
    return ns + [n for n in _ARCHS if n.startswith("paper-")] if include_paper else ns


PRESETS = ("smoke", "chip", "full")


def get_module(name: str):
    return importlib.import_module(_ARCHS[name])


def get_preset(name: str, preset: str) -> ArchConfig:
    """``preset``: ``smoke`` (CPU toy), ``chip`` (one chip's share at the
    published widths, where the config defines one) or ``full``."""
    if preset not in PRESETS:
        raise ValueError(f"unknown preset {preset!r}: valid are {PRESETS}")
    mod = get_module(name)
    if not hasattr(mod, preset.upper()):
        raise ValueError(f"{name} defines no {preset!r} preset")
    return getattr(mod, preset.upper())


def get_full(name: str) -> ArchConfig:
    return get_preset(name, "full")


def get_smoke(name: str) -> ArchConfig:
    return get_preset(name, "smoke")
