"""Config registry: ``get_config(arch_id)`` resolves any assigned arch."""
from repro.configs.base import (INPUT_SHAPES, InputShape, MLAConfig,
                                ModelConfig, MoEConfig, Segment, SSMConfig,
                                depth_cut, flops_per_token, reduced,
                                uniform_segments)
from repro.configs.archs import ARCHS, supported_pairs
from repro.configs.paper_cnn import PAPER_CNN, CNNConfig


def get_config(arch_id: str) -> ModelConfig:
    try:
        return ARCHS[arch_id]
    except KeyError:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {sorted(ARCHS)}") from None


__all__ = [
    "ARCHS", "INPUT_SHAPES", "InputShape", "MLAConfig", "ModelConfig",
    "MoEConfig", "Segment", "SSMConfig", "depth_cut", "get_config", "reduced",
    "uniform_segments", "supported_pairs", "flops_per_token", "PAPER_CNN",
    "CNNConfig",
]
