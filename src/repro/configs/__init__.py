from repro.configs.base import (SHAPES, SMOKE_SHAPE, AudioConfig, MLAConfig,
                                ModelConfig, MoEConfig, ParallelConfig,
                                RWKVConfig, ShapeConfig, SSMConfig,
                                TrainConfig, VisionConfig, YarnConfig,
                                reduce_config, shape_applicable)
from repro.configs.registry import (ASSIGNED_ARCHS, PAPER_ARCHS, get_config,
                                    get_reduced_config, get_shape, iter_cells,
                                    list_archs)

__all__ = [
    "SHAPES", "SMOKE_SHAPE", "AudioConfig", "MLAConfig", "ModelConfig",
    "MoEConfig", "ParallelConfig", "RWKVConfig", "ShapeConfig", "SSMConfig",
    "TrainConfig", "VisionConfig", "YarnConfig", "reduce_config",
    "shape_applicable", "ASSIGNED_ARCHS", "PAPER_ARCHS", "get_config", "get_reduced_config", "get_shape",
    "iter_cells", "list_archs",
]
