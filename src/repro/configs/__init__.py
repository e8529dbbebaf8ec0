"""Architecture registry: one module per assigned arch (+ the paper's own
models) and the assigned input-shape sets.

``get_config(name, reduced=False)`` resolves an arch id (dash or underscore
form) to its :class:`ModelConfig`; ``SHAPES``/``cells()`` enumerate the
assigned (arch x shape) dry-run grid.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Iterator

from repro.models.config import ModelConfig

ARCH_IDS = [
    "granite-8b",
    "nemotron-4-340b",
    "qwen1.5-4b",
    "minicpm3-4b",
    "mamba2-370m",
    "pixtral-12b",
    "grok-1-314b",
    "olmoe-1b-7b",
    "whisper-large-v3",
    "zamba2-2.7b",
]

# the paper's own evaluation models (Table "Evaluated traces and models")
PAPER_IDS = ["llama3-8b", "mistral-24b", "qwen2.5-72b"]


def _modname(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "_")


def get_config(name: str, *, reduced: bool = False, one_chip: bool = False) -> ModelConfig:
    """``reduced``: the tiny CPU-test variant.  ``one_chip``: published
    widths with depth cut to what one 16 GiB chip holds (``ONE_CHIP`` in the
    arch's module; only archs that define one)."""
    if reduced and one_chip:
        raise ValueError("reduced and one_chip are exclusive variants")
    mod = importlib.import_module(f"repro.configs.{_modname(name)}")
    if one_chip:
        if not hasattr(mod, "ONE_CHIP"):
            raise ValueError(f"{name} has no one-chip variant")
        return mod.ONE_CHIP
    return mod.REDUCED if reduced else mod.CONFIG


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode' | 'long_decode'


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "long_decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeSpec) -> bool:
    """long_500k requires sub-quadratic sequence mixing (SSM/hybrid); all
    assigned archs have decoders, so every other cell runs."""
    if shape.kind == "long_decode":
        return cfg.supports_long_context
    return True


def cells(include_skipped: bool = False) -> Iterator[tuple[str, str, bool]]:
    """Yield (arch, shape, applicable) for the 40-cell assignment grid."""
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for sname, sh in SHAPES.items():
            ok = shape_applicable(cfg, sh)
            if ok or include_skipped:
                yield arch, sname, ok
