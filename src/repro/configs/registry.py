"""Architecture registry: ``--arch <id>`` resolves here."""

from __future__ import annotations

from .base import ArchConfig, SHAPES, ShapeConfig, cell_supported

_REGISTRY: dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    assert cfg.name not in _REGISTRY, cfg.name
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    if name not in _REGISTRY:
        _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list[str]:
    _load_all()
    return sorted(_REGISTRY)


def assigned_archs() -> list[str]:
    """The LM architectures (excludes the paper's own DWN models)."""
    _load_all()
    return sorted(n for n, c in _REGISTRY.items() if c.family != "dwn")


_LOADED = False


def _load_all():
    global _LOADED
    if _LOADED:
        return
    from . import (granite_moe_3b_a800m, mixtral_8x7b, whisper_large_v3,  # noqa
                   mamba2_1_3b, qwen3_8b, phi3_mini_3_8b, qwen2_7b,
                   qwen3_14b, recurrentgemma_2b, llava_next_34b, dwn_jsc,
                   dwn_mnist, dwn_lm_head, granite_4_0_h_small)
    _LOADED = True
