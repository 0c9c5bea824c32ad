"""WorkerGroup: the gang of train-worker actors.

ray: python/ray/train/_internal/worker_group.py:92 (WorkerGroup), :226
(execute), :251 (execute_async).  Workers are ray_tpu actors with
max_concurrency=2 so the driver can poll session reports while the
(blocking) train function runs in the other slot.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train import run_record
from ray_tpu.train.session import TrainSession, init_session
from ray_tpu.util import tracing


@ray_tpu.remote(max_concurrency=2)
class TrainWorker:
    """One rank of the SPMD train job."""

    def __init__(self, rank: int, world_size: int):
        self.rank = rank
        self.world_size = world_size
        self.session: Optional[TrainSession] = None
        self._spans_seen = 0  # `tracing.lifecycle_count()` at the last poll() that found spans

    # -- backend hooks ----------------------------------------------------
    def run_fn(self, fn: Callable, *args, **kwargs):
        """Execute an arbitrary callable in this worker (backend setup)."""
        return fn(*args, **kwargs)

    def host_info(self) -> Dict[str, Any]:
        import os
        import socket

        return {"hostname": socket.gethostname(), "pid": os.getpid(), "rank": self.rank}

    # -- training ---------------------------------------------------------
    def run_train_fn(
        self,
        train_fn: Callable,
        config: Optional[Dict],
        resume_ckpt,
        dataset_shards: Optional[Dict[str, Any]] = None,
    ):
        self.session = init_session(
            rank=self.rank,
            world_size=self.world_size,
            resume_checkpoint=resume_ckpt,
            dataset_shards=dataset_shards,
        )
        with tracing.span("train::worker::run_train_fn", attrs={"rank": self.rank}, lifecycle=True) as ctx:
            # A compile on a thread the train function starts parents here.
            run_record.set_fallback_parent(ctx)
            try:
                import inspect

                sig = inspect.signature(train_fn)
                if len(sig.parameters) == 0:
                    train_fn()
                else:
                    train_fn(config or {})
                self.session.done = True
                return {"ok": True}
            except BaseException as e:  # report, don't kill the actor
                self.session.done = True
                self.session.error = e
                raise
            finally:
                run_record.flush_traces()

    def poll(self) -> Dict[str, Any]:
        """Drain buffered session.report() payloads (driver poll loop), and
        with them what this worker has for the run's record: its lifecycle
        spans of the caller's trace not sent yet, its steady steps' rows, its
        stalled steps and the step counters the device has finished."""
        spans, recorded = [], tracing.lifecycle_count()
        ctx = tracing.current_context()
        if ctx and recorded != self._spans_seen:  # nothing to scan in a steady step
            spans = tracing.lifecycle_spans(ctx["trace_id"], since=self._spans_seen)
            self._spans_seen = recorded
        out = {"reports": [], "done": False, "spans": spans, "stalls": run_record.drain_stalls(),
               "step_counters": run_record.drain_step_counters(), "step_series": run_record.drain_step_series(),
               "step_rows": run_record.drain_step_rows(), "tokens_per_step": run_record.set_step_gauges(self.rank)}
        if self.session is not None:
            out["reports"], out["done"] = self.session.drain(), self.session.done
        return out


class WorkerGroup:
    def __init__(
        self,
        num_workers: int,
        resources_per_worker: Optional[Dict[str, float]] = None,
        placement_group=None,
    ):
        self.num_workers = num_workers
        res = dict(resources_per_worker or {"CPU": 1.0})
        base: Dict[str, Any] = {
            "num_cpus": res.pop("CPU", 1.0),
            "resources": res or None,
        }
        self.workers = []
        for i in range(num_workers):
            opts = dict(base)
            if placement_group is not None:
                from ray_tpu.util.scheduling_strategies import (
                    PlacementGroupSchedulingStrategy,
                )

                opts["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                    placement_group, placement_group_bundle_index=i
                )
            self.workers.append(TrainWorker.options(**opts).remote(i, num_workers))

    def execute(self, fn: Callable, *args, timeout: Optional[float] = None, **kwargs) -> List[Any]:
        """Run fn on every worker, wait for all (ray: worker_group.py:226)."""
        return ray_tpu.get(
            [w.run_fn.remote(fn, *args, **kwargs) for w in self.workers],
            timeout=timeout,
        )

    def execute_single(self, idx: int, fn: Callable, *args, timeout=None, **kwargs):
        return ray_tpu.get(self.workers[idx].run_fn.remote(fn, *args, **kwargs), timeout=timeout)

    def execute_async(self, fn: Callable, *args, **kwargs):
        return [w.run_fn.remote(fn, *args, **kwargs) for w in self.workers]

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
