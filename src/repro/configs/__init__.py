"""Architecture registry: 10 assigned archs + the paper's own (qwen3-next
GDN) + Qwen3-Next-80B-A3B's own block (one chip's share)."""
from __future__ import annotations

from repro.configs.base import (ArchConfig, ServingTopology, ShapeConfig,
                                SHAPES, shape_applicable)
from repro.configs.llava_next_34b import CONFIG as llava_next_34b
from repro.configs.minicpm_2b import CONFIG as minicpm_2b
from repro.configs.minitron_8b import CONFIG as minitron_8b
from repro.configs.yi_9b import CONFIG as yi_9b
from repro.configs.h2o_danube_1_8b import CONFIG as h2o_danube_1_8b
from repro.configs.mixtral_8x7b import CONFIG as mixtral_8x7b
from repro.configs.arctic_480b import CONFIG as arctic_480b
from repro.configs.musicgen_medium import CONFIG as musicgen_medium
from repro.configs.mamba2_1_3b import CONFIG as mamba2_1_3b
from repro.configs.recurrentgemma_2b import CONFIG as recurrentgemma_2b
from repro.configs.qwen3_next_gdn import CONFIG as qwen3_next_gdn
from repro.configs.qwen3_next_80b_a3b import CONFIG as qwen3_next_80b_a3b

ARCHS = {
    c.name: c for c in [
        llava_next_34b, minicpm_2b, minitron_8b, yi_9b, h2o_danube_1_8b,
        mixtral_8x7b, arctic_480b, musicgen_medium, mamba2_1_3b,
        recurrentgemma_2b, qwen3_next_gdn, qwen3_next_80b_a3b,
    ]
}

ASSIGNED = [n for n in ARCHS
            if n not in ("qwen3-next-gdn", "qwen3-next-80b-a3b")]


def get_arch(name: str) -> ArchConfig:
    key = name.replace("_", "-")
    if key not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[key]


__all__ = ["ArchConfig", "ServingTopology", "ShapeConfig", "SHAPES",
           "ARCHS", "ASSIGNED", "get_arch", "shape_applicable"]
