"""Architecture registry: ``get_config(arch_id)`` / ``get_smoke_config(arch_id)``.

One module per assigned architecture; each exports ``CONFIG`` (the exact
assigned full-size config, citation in ``source``) and ``smoke()`` (a reduced
same-family variant: ≤2 layers, d_model ≤ 512, ≤4 experts — runs a forward /
train step on CPU in the per-arch smoke tests).
"""

from __future__ import annotations

import importlib

from repro.common.config import INPUT_SHAPES, ModelConfig, ShapeConfig

ARCH_IDS = (
    "mamba2-130m",
    "smollm-135m",
    "deepseek-moe-16b",
    "phi3.5-moe-42b-a6.6b",
    "minitron-8b",
    "qwen2-vl-72b",
    "gemma3-1b",
    "qwen2-1.5b",
    "whisper-small",
    "hymba-1.5b",
)

_MODULES = {
    "mamba2-130m": "mamba2_130m",
    "smollm-135m": "smollm_135m",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe_42b",
    "minitron-8b": "minitron_8b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "gemma3-1b": "gemma3_1b",
    "qwen2-1.5b": "qwen2_1_5b",
    "whisper-small": "whisper_small",
    "hymba-1.5b": "hymba_1_5b",
    # paper's own subject models (reduced-scale stand-ins train end-to-end)
    "llama3-8b": "llama3_8b",
    "tiny-llama": "tiny_llama",
    # sparse experts with sliding and full layers; ":EP8" is one chip's
    # share of its 8-way expert-parallel deployment
    "mellum2-12b": "mellum2_12b",
    "mellum2-12b-ep8": "mellum2_12b:EP8",
}


def _module(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(
            f"unknown arch '{arch_id}'; known: {sorted(_MODULES)}"
        )
    name = _MODULES[arch_id].partition(":")[0]
    return importlib.import_module(f"repro.configs.{name}")


def get_config(arch_id: str) -> ModelConfig:
    """The module's ``CONFIG``, or the attribute after a ``:`` in its
    registry entry."""
    attr = _MODULES.get(arch_id, "").partition(":")[2] or "CONFIG"
    return getattr(_module(arch_id), attr)


def get_smoke_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).smoke()


def get_shape(name: str) -> ShapeConfig:
    return INPUT_SHAPES[name]


def shape_applicable(arch_id: str, shape_name: str) -> tuple[bool, str]:
    """(runs?, reason) — DESIGN.md §5 skip matrix for long_500k etc."""
    cfg = get_config(arch_id)
    if shape_name != "long_500k":
        return True, ""
    if cfg.uses_ssm:  # mamba2, hymba
        return True, "ssm/hybrid: constant state + windowed attention"
    if cfg.attn is not None and (cfg.attn.sliding_window > 0):
        return True, "sliding-window attention bounds per-layer cache"
    return False, (
        "pure full-attention arch: 524k decode cache is quadratic-history; "
        "skipped per spec (DESIGN.md §5)"
    )
