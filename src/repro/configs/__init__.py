"""Architecture registry: ``get_config("<arch-id>")`` / ``--arch <id>``.

Eleven assigned architectures (exact published dims, one module each) plus
the framework's own demo config.  ``get_smoke_config`` returns the
reduced same-family variant used by CPU smoke tests.
"""

from __future__ import annotations

import importlib

from repro.models.config import ModelConfig, smoke_variant

# arch-id -> module name
_REGISTRY: dict[str, str] = {
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "smollm-360m": "smollm_360m",
    "gemma3-1b": "gemma3_1b",
    "mistral-large-123b": "mistral_large_123b",
    "zamba2-1.2b": "zamba2_1_2b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
    "mixtral-8x22b": "mixtral_8x22b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "mamba2-1.3b": "mamba2_1_3b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "repro-100m": "repro_100m",
}

ASSIGNED_ARCHS: tuple[str, ...] = tuple(k for k in _REGISTRY if k != "repro-100m")


def list_archs() -> list[str]:
    return list(_REGISTRY)


def get_config(arch: str) -> ModelConfig:
    if arch not in _REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_REGISTRY)}")
    mod = importlib.import_module(f"repro.configs.{_REGISTRY[arch]}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return smoke_variant(get_config(arch))
