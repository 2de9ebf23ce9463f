"""ray_tpu.train — distributed training.

Two composable layers, mirroring the reference's split between Train (actor
orchestration) and the in-worker training loop:

- Orchestration: `DataParallelTrainer`/`JaxTrainer` + controller/worker-group
  (reference: train/v2/api/data_parallel_trainer.py:64).
- In-program SPMD: `make_train_step`/`make_sp_pp_train_step` build jitted
  sharded steps over a jax Mesh (TPU-native replacement for torch DDP/FSDP).
"""

from ray_tpu.train import storage
from ray_tpu.train._checkpoint import Checkpoint
from ray_tpu.train.backend import BackendConfig, JaxConfig, TorchConfig
from ray_tpu.train.config import (
    CheckpointConfig,
    FailureConfig,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.train.session import (
    broadcast_from_rank_zero,
    collective_barrier,
    get_checkpoint,
    get_context,
    get_dataset_shard,
    report,
    report_opt_state,
)
from ray_tpu.train import zero
from ray_tpu.train.zero import (
    ZeroShardedOptimizer,
    make_zero_train_step,
    match_partition_rules,
)
from ray_tpu.train.spmd import (
    init_opt_state,
    init_sharded,
    make_sp_pp_train_step,
    make_train_step,
)
from ray_tpu.train.trainer import (
    DataParallelTrainer,
    JaxTrainer,
    TorchTrainer,
    Result,
    TrainingFailedError,
)

__all__ = [
    "BackendConfig",
    "Checkpoint",
    "CheckpointConfig",
    "DataParallelTrainer",
    "FailureConfig",
    "JaxConfig",
    "JaxTrainer",
    "TorchTrainer",
    "Result",
    "RunConfig",
    "ScalingConfig",
    "TorchConfig",
    "TrainingFailedError",
    "broadcast_from_rank_zero",
    "collective_barrier",
    "get_checkpoint",
    "get_context",
    "get_dataset_shard",
    "init_opt_state",
    "init_sharded",
    "make_sp_pp_train_step",
    "make_train_step",
    "report",
    "report_opt_state",
    "storage",
    "zero",
    "ZeroShardedOptimizer",
    "make_zero_train_step",
    "match_partition_rules",
]
