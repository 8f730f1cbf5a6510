"""Architecture registry: every assigned arch id → ModelConfig.

``get_config(arch_id)`` / ``--arch <id>`` is the selection mechanism for
launchers and dry-runs.  ``runnable_cells()`` enumerates the
assigned (arch × shape) grid with the documented long_500k skips.
"""

from __future__ import annotations

from repro.configs.base import (
    ALL_SHAPES,
    SHAPES_BY_NAME,
    ModelConfig,
    ShapeConfig,
    reduced,
)
from repro.configs import (
    grok1_314b,
    h2o_danube_1p8b,
    internvl2_2b,
    llama4_maverick_400b,
    olmo_1b,
    qwen2p5_14b,
    recurrentgemma_2b,
    smollm_135m,
    whisper_base,
    xlstm_125m,
)

_REGISTRY: dict[str, ModelConfig] = {
    cfg.name: cfg
    for cfg in (
        llama4_maverick_400b.CONFIG,
        grok1_314b.CONFIG,
        h2o_danube_1p8b.CONFIG,
        smollm_135m.CONFIG,
        olmo_1b.CONFIG,
        qwen2p5_14b.CONFIG,
        recurrentgemma_2b.CONFIG,
        whisper_base.CONFIG,
        xlstm_125m.CONFIG,
        internvl2_2b.CONFIG,
    )
}


def list_archs() -> list[str]:
    return sorted(_REGISTRY)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _REGISTRY:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {', '.join(list_archs())}"
        )
    return _REGISTRY[arch_id]


def get_reduced_config(arch_id: str, **overrides) -> ModelConfig:
    return reduced(get_config(arch_id), **overrides)


def cell_skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    """None if the (arch × shape) cell runs; otherwise the documented skip."""
    if shape.kind == "long_decode" and not cfg.subquadratic:
        return (
            "long_500k requires sub-quadratic attention; "
            f"{cfg.name} is pure full-attention (DESIGN.md §6)"
        )
    return None


def runnable_cells() -> list[tuple[str, str]]:
    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        for shape in ALL_SHAPES:
            if cell_skip_reason(cfg, shape) is None:
                cells.append((arch, shape.name))
    return cells


def skipped_cells() -> list[tuple[str, str, str]]:
    out = []
    for arch in list_archs():
        cfg = get_config(arch)
        for shape in ALL_SHAPES:
            reason = cell_skip_reason(cfg, shape)
            if reason:
                out.append((arch, shape.name, reason))
    return out


def get_shape(name: str) -> ShapeConfig:
    return SHAPES_BY_NAME[name]
