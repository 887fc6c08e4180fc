"""Assigned architecture configs (``--arch <id>``).

Each module defines ``CONFIG`` (the exact assigned full-scale config, with
source citation) and ``SMOKE`` (a reduced same-family variant: <=2 layers,
d_model<=512, <=4 experts) used by CPU smoke tests.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from ..models.config import ModelConfig

ARCH_IDS: List[str] = [
    "mamba2_2p7b",
    "whisper_large_v3",
    "qwen3_moe_30b_a3b",
    "qwen2_moe_a2p7b",
    "chameleon_34b",
    "qwen2_0p5b",
    "qwen2p5_14b",
    "smollm_360m",
    "hymba_1p5b",
    "mistral_large_123b",
    "granite_4_0_h_micro",
]

# extra pool architectures (beyond those above; see README)
EXTRA_ARCH_IDS: List[str] = [
    "llama3_8b",
    "mixtral_8x7b",
]

# the task-assignment names -> module names
ALIASES: Dict[str, str] = {
    "mamba2-2.7b": "mamba2_2p7b",
    "whisper-large-v3": "whisper_large_v3",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2p7b",
    "chameleon-34b": "chameleon_34b",
    "qwen2-0.5b": "qwen2_0p5b",
    "qwen2.5-14b": "qwen2p5_14b",
    "smollm-360m": "smollm_360m",
    "hymba-1.5b": "hymba_1p5b",
    "mistral-large-123b": "mistral_large_123b",
    "llama3-8b": "llama3_8b",
    "mixtral-8x7b": "mixtral_8x7b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    mod_name = ALIASES.get(arch, arch).replace("-", "_").replace(".", "p")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.SMOKE if smoke else mod.CONFIG


def all_configs(smoke: bool = False):
    return {a: get_config(a, smoke) for a in ARCH_IDS}
