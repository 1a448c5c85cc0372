"""Config registry: ``--arch <id>`` resolution for every launcher.

Includes the 10 assigned architectures and the paper's own Llama
pre-training ladder (Table 10) used by the reproduction benchmarks.
"""

from __future__ import annotations

import importlib

from repro.models.config import ModelConfig, reduce_for_smoke

# assigned architecture id -> module (exact configs from the assignment)
_ARCH_MODULES = {
    "minicpm3-4b": "repro.configs.minicpm3_4b",
    "stablelm-12b": "repro.configs.stablelm_12b",
    "gemma2-27b": "repro.configs.gemma2_27b",
    "qwen1.5-4b": "repro.configs.qwen1_5_4b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "llama4-maverick-400b-a17b": "repro.configs.llama4_maverick",
    "qwen2-vl-2b": "repro.configs.qwen2_vl_2b",
    "zamba2-7b": "repro.configs.zamba2_7b",
    "xlstm-125m": "repro.configs.xlstm_125m",
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t_large_v2",
}


def _llama(name, layers, d, heads, ff) -> ModelConfig:
    """Paper Table 10 Llama-based pre-training architectures."""
    return ModelConfig(
        name=name, family="decoder", n_layers=layers, d_model=d,
        n_heads=heads, n_kv_heads=heads, d_ff=ff, vocab_size=32000,
        rope_theta=10000.0, vocab_round=64)


# paper's pre-training ladder (hidden/intermediate/heads/layers, Table 10)
_PAPER_MODELS = {
    "llama-60m": _llama("llama-60m", 8, 512, 8, 1376),
    "llama-130m": _llama("llama-130m", 12, 768, 12, 2048),
    "llama-350m": _llama("llama-350m", 24, 1024, 16, 2736),
    "llama-1b": _llama("llama-1b", 24, 2048, 32, 5461),
    "llama-3b": _llama("llama-3b", 32, 2560, 32, 6848),
    "llama-7b": _llama("llama-7b", 32, 4096, 32, 11008),
    # ~100M model for the end-to-end example driver
    "llama-100m": _llama("llama-100m", 12, 640, 10, 1708),
}

# paper Table 10 low-rank ranks per model size
PAPER_RANKS = {
    "llama-60m": 128, "llama-130m": 256, "llama-350m": 256,
    "llama-1b": 512, "llama-3b": 512, "llama-7b": 1024,
    "llama-100m": 128,
}

ASSIGNED_ARCHS = tuple(_ARCH_MODULES)


def arch_names() -> list[str]:
    return sorted(list(_ARCH_MODULES) + list(_PAPER_MODELS))


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    """Resolve an architecture id; ``smoke=True`` returns the reduced
    same-family config used by CPU smoke tests."""
    if name in _ARCH_MODULES:
        cfg = importlib.import_module(_ARCH_MODULES[name]).CONFIG
    elif name in _PAPER_MODELS:
        cfg = _PAPER_MODELS[name]
    else:
        raise ValueError(f"unknown arch {name!r}; options: {arch_names()}")
    return reduce_for_smoke(cfg) if smoke else cfg
