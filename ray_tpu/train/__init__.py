"""ray_tpu.train: distributed SPMD training over the actor runtime.

ray: python/ray/train/ — trainers spawn a gang of worker actors, the backend
joins them into one process group, the user loop reports metrics/checkpoints
(SURVEY.md §3.5).  TPU-native: the "process group" is the multi-host XLA
runtime; gradient communication is compiled into the train step, not a
runtime collective library.
"""

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import (
    CheckpointConfig,
    FailureConfig,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.air.result import Result
from ray_tpu.train import session
from ray_tpu.train.backend import Backend, BackendConfig, JaxConfig
from ray_tpu.train.backend_executor import BackendExecutor, TrainingFailedError
from ray_tpu.train.data_parallel_trainer import DataParallelTrainer, JaxTrainer
from ray_tpu.train.run_record import last_run_record
from ray_tpu.train.session import (
    get_checkpoint,
    get_dataset_shard,
    get_world_rank,
    get_world_size,
    report,
)
from ray_tpu.train.worker_group import WorkerGroup

__all__ = [
    "Backend",
    "BackendConfig",
    "BackendExecutor",
    "Checkpoint",
    "CheckpointConfig",
    "DataParallelTrainer",
    "FailureConfig",
    "JaxConfig",
    "JaxTrainer",
    "Result",
    "RunConfig",
    "ScalingConfig",
    "TrainingFailedError",
    "WorkerGroup",
    "get_checkpoint",
    "get_dataset_shard",
    "get_world_rank",
    "get_world_size",
    "last_run_record",
    "report",
    "session",
]
