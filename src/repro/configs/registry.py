"""Architecture registry: ``--arch <id>`` resolution + shape applicability."""

from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig

_ARCH_MODULES: Dict[str, str] = {
    "h2o-danube-1.8b": "repro.configs.h2o_danube_1_8b",
    "minitron-8b": "repro.configs.minitron_8b",
    "deepseek-7b": "repro.configs.deepseek_7b",
    "stablelm-3b": "repro.configs.stablelm_3b",
    "paligemma-3b": "repro.configs.paligemma_3b",
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t_large_v2",
    "llama4-maverick-400b-a17b": "repro.configs.llama4_maverick_400b_a17b",
    "phi3.5-moe-42b-a6.6b": "repro.configs.phi3_5_moe_42b_a6_6b",
    "xlstm-1.3b": "repro.configs.xlstm_1_3b",
    "jamba-1.5-large-398b": "repro.configs.jamba_1_5_large_398b",
}

ASSIGNED_ARCHS: List[str] = list(_ARCH_MODULES)


def get(name: str) -> ModelConfig:
    mod = importlib.import_module(_ARCH_MODULES[name])
    return mod.CONFIG


def get_smoke(name: str) -> ModelConfig:
    mod = importlib.import_module(_ARCH_MODULES[name])
    return mod.smoke_config()


def is_smoke(name: str, cfg: ModelConfig) -> bool:
    """Is ``cfg`` the reduced (smoke) config of registry arch ``name``?"""
    return name in _ARCH_MODULES and cfg == get_smoke(name)


def for_plan(plan) -> ModelConfig:
    """The config an ``ExecutionPlan`` was built on: the reduced one for
    smoke plans, the published widths otherwise."""
    return get_smoke(plan.arch) if plan.smoke else get(plan.arch)


def paper_model(name: str) -> ModelConfig:
    from repro.configs import paper_models

    return {"gpt-3b": paper_models.GPT_3B, "gpt-7b": paper_models.GPT_7B,
            "dit-1b": paper_models.DIT_1B}[name]


def shape_supported(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """(supported, reason). long_500k needs sub-quadratic attention."""
    if shape.name == "long_500k":
        subq = cfg.window is not None or cfg.family in ("ssm", "hybrid")
        if not subq:
            return False, (
                "long_500k skipped: pure full-attention arch (no SWA/SSM); "
                "see DESIGN.md §Arch-applicability")
    return True, ""


def cells(archs=None) -> List[Tuple[str, str, bool, str]]:
    """All (arch, shape, supported, reason) assignment cells."""
    out = []
    for a in archs or ASSIGNED_ARCHS:
        cfg = get(a)
        for s in SHAPES.values():
            ok, why = shape_supported(cfg, s)
            out.append((a, s.name, ok, why))
    return out
