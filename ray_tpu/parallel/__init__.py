from jax import shard_map

from ray_tpu.parallel.mesh import (
    AXES,
    DEFAULT_RULES,
    MeshSpec,
    ShardingRules,
    act_sharding,
    constrain,
    hybrid_mesh,
    param_shardings,
    sharding_for,
)
from ray_tpu.parallel import collectives
from ray_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
from ray_tpu.parallel.ring_attention import reference_attention, ring_attention

__all__ = [
    "AXES",
    "DEFAULT_RULES",
    "MeshSpec",
    "hybrid_mesh",
    "ShardingRules",
    "act_sharding",
    "collectives",
    "constrain",
    "param_shardings",
    "pipeline_apply",
    "reference_attention",
    "ring_attention",
    "shard_map",
    "sharding_for",
    "stack_stage_params",
]
