"""Architecture registry: ``get_config(arch_id)`` / ``ARCHS``."""
from __future__ import annotations

from repro.configs.base import (
    ALL_SHAPES,
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    SHAPES_BY_NAME,
    TRAIN_4K,
    ModelConfig,
    MoEConfig,
    ShapeConfig,
    applicable_shapes,
    reduced,
    reduced_shape,
)

from repro.configs import (  # noqa: E402
    edgenext_s,
    granite_4_0_h_micro,
    h2o_danube_1_8b,
    minitron_4b,
    olmo_1b,
    qwen2_moe_a2_7b,
    qwen2_vl_2b,
    qwen3_moe_30b_a3b,
    recurrentgemma_2b,
    rwkv6_1_6b,
    seamless_m4t_large_v2,
    starcoder2_15b,
)

ARCHS = {
    "starcoder2-15b": starcoder2_15b.CONFIG,
    "minitron-4b": minitron_4b.CONFIG,
    "h2o-danube-1.8b": h2o_danube_1_8b.CONFIG,
    "olmo-1b": olmo_1b.CONFIG,
    "qwen3-moe-30b-a3b": qwen3_moe_30b_a3b.CONFIG,
    "qwen2-moe-a2.7b": qwen2_moe_a2_7b.CONFIG,
    "recurrentgemma-2b": recurrentgemma_2b.CONFIG,
    "rwkv6-1.6b": rwkv6_1_6b.CONFIG,
    "seamless-m4t-large-v2": seamless_m4t_large_v2.CONFIG,
    "qwen2-vl-2b": qwen2_vl_2b.CONFIG,
    "granite-4.0-h-micro": granite_4_0_h_micro.CONFIG,
}

EDGENEXT_S = edgenext_s.CONFIG


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; choose from {sorted(ARCHS)}")
    cfg = ARCHS[arch]
    cfg.validate()
    return cfg


__all__ = [
    "ARCHS",
    "EDGENEXT_S",
    "ALL_SHAPES",
    "SHAPES_BY_NAME",
    "TRAIN_4K",
    "PREFILL_32K",
    "DECODE_32K",
    "LONG_500K",
    "ModelConfig",
    "MoEConfig",
    "ShapeConfig",
    "applicable_shapes",
    "get_config",
    "reduced",
    "reduced_shape",
]
