from repro.configs.base import (ATTN, MAMBA, MLSTM, SLSTM, AttnKind,
                                FrontendConfig, MeshConfig, ModelConfig,
                                MoEConfig, MULTI_POD_MESH, OptimizerConfig,
                                PrecisionConfig, RopeConfig, RunConfig, SHAPES,
                                ShapeConfig, ShardingConfig, SINGLE_POD_MESH,
                                SSMConfig, UNIT_MESH, XLSTMConfig,
                                arch_defaults, get_model_config, list_archs,
                                make_run_config, register, shape_applicable)

__all__ = [
    "ATTN", "MAMBA", "MLSTM", "SLSTM", "AttnKind", "FrontendConfig",
    "MeshConfig", "ModelConfig", "MoEConfig", "MULTI_POD_MESH",
    "OptimizerConfig", "PrecisionConfig", "RopeConfig", "RunConfig",
    "SHAPES", "ShapeConfig", "ShardingConfig", "SINGLE_POD_MESH",
    "SSMConfig", "UNIT_MESH", "XLSTMConfig",
    "arch_defaults", "get_model_config", "list_archs", "make_run_config",
    "register", "shape_applicable",
]
