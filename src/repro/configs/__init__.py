"""Architecture registry: ``--arch <id>`` → exact published config.

Every assigned architecture has ``configs/<id>.py`` with ``config()``
(the published widths and depth: what ``--full`` runs on a chip, and what
the dry-run plans for a pod) and ``smoke_config()`` (reduced, CPU-testable).
"""
from . import (dbrx, llama3_8b, mamba2_130m, nemotron4_340b, phi35_moe,
               qwen2_vl, qwen3_1p7b, qwen15_32b, recurrentgemma_9b,
               whisper_tiny)
from .base import ModelConfig

_MODULES = {
    m.ARCH: m
    for m in (phi35_moe, dbrx, whisper_tiny, qwen2_vl, mamba2_130m,
              qwen3_1p7b, qwen15_32b, nemotron4_340b, llama3_8b,
              recurrentgemma_9b)
}

ARCHS = tuple(_MODULES)

#: assigned input shapes: name → (seq_len, global_batch, kind)
SHAPES = {
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}


def get_config(arch: str) -> ModelConfig:
    return _MODULES[arch].config()


def get_smoke_config(arch: str) -> ModelConfig:
    return _MODULES[arch].smoke_config()


def list_archs():
    return list(ARCHS)


def shape_applicable(cfg: ModelConfig, shape: str) -> bool:
    """long_500k needs sub-quadratic attention (assignment; DESIGN.md §5)."""
    if shape == "long_500k":
        return cfg.subquadratic
    return True
