from .base import (CacheConfig, ModelConfig, MoEConfig, OptimizerConfig,
                   RuntimeConfig, SHAPES, ShapeConfig, SSMConfig, reduced,
                   with_layers)
from .registry import get_config, list_archs, register

__all__ = [
    "CacheConfig", "ModelConfig", "MoEConfig", "OptimizerConfig",
    "RuntimeConfig", "SHAPES", "ShapeConfig", "SSMConfig", "reduced",
    "with_layers",
    "get_config", "list_archs", "register",
]
