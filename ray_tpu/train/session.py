"""Per-worker train session: report queue + rank info.

ray: python/ray/train/_internal/session.py:63 (_TrainSession, report queue
:120/:171) and python/ray/air/session.py (the user-facing facade).  The user
train loop calls session.report(metrics, checkpoint=...) — reports buffer in
the worker actor and are drained by the driver's BackendExecutor poll loop.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.train import run_record
from ray_tpu.util import tracing

_session: Optional["TrainSession"] = None


class TrainSession:
    def __init__(
        self,
        rank: int,
        world_size: int,
        local_rank: int = 0,
        resume_checkpoint: Optional[Checkpoint] = None,
        experiment_name: str = "train",
        dataset_shards: Optional[Dict[str, Any]] = None,
    ):
        self.rank = rank
        self.world_size = world_size
        self.local_rank = local_rank
        self.resume_checkpoint = resume_checkpoint
        self.experiment_name = experiment_name
        self.dataset_shards = dataset_shards or {}
        self._lock = threading.Lock()
        self._reports: List[Dict[str, Any]] = []
        self.done = False
        self.error: Optional[BaseException] = None

    def report(self, metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None):
        # "t" stamps the report beside the payload: the driver takes the
        # seconds to its `on_report` from it (train/run_record.py).  The call's
        # own seconds go to the step that is open, and under a profiler the
        # span sits beside `train_step/*` on the device planes' clock.
        t0 = time.perf_counter()
        with tracing.annotate("train/report"), self._lock:
            self._reports.append({"metrics": dict(metrics), "checkpoint": checkpoint, "t": time.time()})
        run_record.add_report_seconds(time.perf_counter() - t0)

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            out = self._reports
            self._reports = []
            return out


def init_session(**kwargs) -> TrainSession:
    global _session
    _session = TrainSession(**kwargs)
    return _session


def get_session() -> TrainSession:
    if _session is None:
        raise RuntimeError(
            "No train session active — this API must run inside a train worker"
        )
    return _session


def shutdown_session():
    global _session
    _session = None


# -- user-facing facade (ray: python/ray/air/session.py) -------------------


def report(metrics: Dict[str, Any], *, checkpoint: Optional[Checkpoint] = None) -> None:
    get_session().report(metrics, checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    return get_session().resume_checkpoint


def get_dataset_shard(name: str = "train"):
    """This rank's Dataset shard (ray: session.get_dataset_shard) — block
    refs resolve worker-side, so iteration never round-trips the driver."""
    shards = get_session().dataset_shards
    if name not in shards:
        raise KeyError(
            f"no dataset shard {name!r}; trainer datasets: {sorted(shards)}"
        )
    return shards[name]


def get_world_rank() -> int:
    return get_session().rank


def get_world_size() -> int:
    return get_session().world_size


def get_local_rank() -> int:
    return get_session().local_rank


def get_experiment_name() -> str:
    return get_session().experiment_name
