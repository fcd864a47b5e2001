"""Architecture registry: one module per assigned architecture.

Each module exposes CONFIG (exact published hyper-parameters) and
REDUCED (same family, CPU-smoke-test sized).
"""
from repro.configs.base import (SHAPES, ArchConfig, InputShape,
                                shape_applicable)

__all__ = ["ArchConfig", "InputShape", "SHAPES", "shape_applicable",
           "ARCHS", "ARCH_NAMES", "get_config"]

_ARCH_MODULES = [
    "dbrx_132b", "granite_moe_1b_a400m", "nemotron_4_15b", "qwen2_5_3b",
    "command_r_35b", "minicpm_2b", "qwen2_vl_2b", "xlstm_125m",
    "whisper_base", "zamba2_2_7b", "kanana_2_30b_a3b",
]


def _load():
    import importlib
    archs = {}
    for m in _ARCH_MODULES:
        mod = importlib.import_module(f"repro.configs.{m}")
        archs[mod.CONFIG.name] = (mod.CONFIG, mod.REDUCED)
    return archs


ARCHS = _load()
ARCH_NAMES = list(ARCHS.keys())


def get_config(name: str, reduced: bool = False) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_NAMES}")
    return ARCHS[name][1 if reduced else 0]
