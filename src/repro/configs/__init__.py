"""Assigned-architecture registry: importing this package registers all configs."""
from repro.configs.base import (  # noqa: F401
    ARCH_REGISTRY,
    ArchConfig,
    MoEConfig,
    SHAPES,
    SSMConfig,
    ShapeConfig,
    get_arch,
    reduced,
)
from repro.configs import (  # noqa: F401
    whisper_base,
    stablelm_12b,
    qwen2_5_32b,
    granite_3_2b,
    qwen1_5_110b,
    zamba2_1_2b,
    granite_moe_3b_a800m,
    granite_4_0_h_micro,
    llama4_maverick_400b_a17b,
    llava_next_34b,
    mamba2_780m,
    paper_models,
)

__all__ = [
    "ARCH_REGISTRY", "ArchConfig", "MoEConfig", "SSMConfig", "ShapeConfig",
    "SHAPES", "get_arch", "reduced",
]
