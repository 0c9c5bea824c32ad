"""BackendExecutor: owns the worker group + backend lifecycle and the
training poll loop.

ray: python/ray/train/_internal/backend_executor.py:43 (start :94,
start_training :315).  Differences by design: reports are pulled via actor
polling (the worker actors run the blocking train fn in one concurrency slot
and answer poll() in the other), and failure handling restarts the WHOLE
group — an SPMD mesh program cannot lose a single rank (SURVEY.md §7
"SPMD meets actors").
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import ScalingConfig
from ray_tpu.train.backend import BackendConfig
from ray_tpu.train.run_record import RunRecord
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import tracing


class TrainingFailedError(RuntimeError):
    pass


class RemeshScaleUp(Exception):
    """Internal control flow, not a failure: the head signalled that a
    shrunk MESH gang can scale back to full size (pg_info scale_up_ready).
    run_training raises it so the trainer can tear down, pg_reshape, and
    restart at the original world size from the latest checkpoint."""


def _record_creation_stages(spawn_ctx, worker, host) -> None:
    """Attach to the spawn what the head already stamped on rank 0's creation
    task (TaskRecord.stages: pending, queued, lease, wire, running, ...), as
    one child span `train::worker_group::creation_task`; nothing where the
    head's task events are out of reach."""
    try:
        from ray_tpu.util.state import list_tasks

        event = next(
            (t for t in list_tasks(limit=1 << 20)
             if t.get("creation") and t.get("actor_id") == worker._id), None)
    except Exception:  # noqa: BLE001: an observation; the start goes on without it
        return
    if event is None or not event.get("stages"):
        return
    stamps = [v for v in event["stages"].values() if isinstance(v, (int, float))]
    tracing.record_span(
        "train::worker_group::creation_task", min(stamps), max(stamps), parent=spawn_ctx,
        attrs={"pid": host["pid"], **{f"{k}_s": v for k, v in (event.get("durations") or {}).items()}},
        lifecycle=True,
    )


class BackendExecutor:
    def __init__(
        self,
        backend_config: BackendConfig,
        scaling_config: Optional[ScalingConfig] = None,
        record: Optional[RunRecord] = None,
    ):
        # The fit()'s run record: every poll reply is folded into it.
        self.record = record
        self.backend_config = backend_config
        self.backend = backend_config.backend_cls()()
        self.scaling = scaling_config or ScalingConfig()
        self.worker_group: Optional[WorkerGroup] = None
        self._pg = None
        # Elastic MESH gangs: generation of the reservation the current
        # worker group was spawned into; the head bumps it on every re-mesh.
        self._elastic = False
        self._generation = 0
        self.num_started_workers = 0
        # How long start() waits for the gang reservation before failing
        # with the PG state + unplaceable bundles (tests shrink this).
        self.pg_wait_timeout_s = 60.0

    # -- lifecycle --------------------------------------------------------
    def start(self, num_workers: Optional[int] = None):
        """Spawn the worker group (no-op if already started).

        The placement group is created ONCE and survives stop_workers():
        elastic restarts re-spawn workers into the re-meshed gang.
        num_workers overrides the scaling config's count (elastic MESH
        gangs restart at the gang's current — possibly shrunk — size)."""
        if self.worker_group is not None:
            return
        # Lifecycle spans (util/tracing.py): recorded in every run.
        with tracing.span("train::executor::start", lifecycle=True):
            self._start(num_workers)

    def _start(self, num_workers: Optional[int]) -> None:
        sc = self.scaling
        n = sc.num_workers if num_workers is None else num_workers
        if sc.num_workers > 1:
            with tracing.span("train::executor::placement_group", lifecycle=True):
                n = self._reserve_gang(n)
        self.num_started_workers = n
        with tracing.span("train::worker_group::spawn", attrs={"num_workers": n}, lifecycle=True) as ctx:
            self.worker_group = WorkerGroup(
                n, sc.worker_resources(), placement_group=self._pg
            )
            # Spawned = the first call answered: the actors' creation (lease,
            # a fork or a warm worker, __init__) is behind it.
            hosts = ray_tpu.get(
                [w.host_info.remote() for w in self.worker_group.workers], timeout=60
            )
        _record_creation_stages(ctx, self.worker_group.workers[0], hosts[0])
        with tracing.span("train::backend::on_start", lifecycle=True):
            self.backend.on_start(self.worker_group, self.backend_config)

    def _reserve_gang(self, n: int) -> int:
        """Gang-reserve the workers' resources (ray: Train reserves a PG per
        trial via Tune — base_trainer.py:52 path); returns the world size."""
        sc = self.scaling
        if self._pg is None:
            from ray_tpu.util.placement_group import placement_group

            bundles = [sc.worker_resources() for _ in range(sc.num_workers)]
            self._pg = placement_group(
                bundles, strategy=sc.placement_strategy
            )
        if not self._pg.wait(timeout_seconds=self.pg_wait_timeout_s):
            info = self.pg_info() or {}
            placed = set(info.get("bundle_nodes") or {})
            unplaced = [
                i
                for i in range(len(self._pg.bundle_specs))
                if i not in placed
            ]
            raise TrainingFailedError(
                f"placement group {self._pg.id} not ready after "
                f"{self.pg_wait_timeout_s:.0f}s: "
                f"state={info.get('state') or 'UNKNOWN'}, unplaceable "
                f"bundles {unplaced} of {self._pg.bundle_specs}; the "
                "cluster cannot satisfy the reservation — check node "
                "resources"
                + (
                    " and mesh_coord labels"
                    if sc.placement_strategy == "MESH"
                    else ""
                )
            )
        self._elastic = sc.placement_strategy == "MESH"
        info = self.pg_info() or {}
        self._generation = info.get("generation", 0)
        if self._elastic:
            n = min(n, info.get("size", n))
        return n

    def stop_workers(self):
        """Tear down the worker group KEEPING the placement group — the
        elastic-restart path re-spawns workers into the re-meshed gang."""
        if self.worker_group is not None:
            try:
                self.backend.on_shutdown(self.worker_group, self.backend_config)
            except Exception:
                pass
            try:
                self.worker_group.shutdown()
            except Exception:
                pass  # gang actors may already be dead (head killed them)
            self.worker_group = None

    def shutdown(self):
        with tracing.span("train::executor::shutdown", lifecycle=True):
            self.stop_workers()
            if self._pg is not None:
                from ray_tpu.util.placement_group import remove_placement_group

                try:
                    remove_placement_group(self._pg)
                except Exception:
                    pass
                self._pg = None

    # -- elastic re-mesh ---------------------------------------------------
    def pg_info(self) -> Optional[Dict[str, Any]]:
        if self._pg is None:
            return None
        from ray_tpu._private.client import client

        return client.pg_info(self._pg.id)

    def remesh_in_progress(self) -> bool:
        """True when the gang the current workers were spawned into no
        longer exists: mid-RESHAPING, or already re-formed at a new
        generation."""
        if not self._elastic:
            return False
        info = self.pg_info()
        return bool(info) and (
            info["state"] == "RESHAPING"
            or info["generation"] != self._generation
        )

    def wait_remesh(self, timeout_seconds: Optional[float] = None) -> Dict:
        """Block until the gang re-forms (CREATED at a new generation);
        returns the final pg_info.  Default timeout covers two head-side
        wait-then-shrink windows plus placement slack."""
        if timeout_seconds is None:
            from ray_tpu._private import config as _config

            timeout_seconds = 2.0 * float(_config.get("remesh_wait_s")) + 60.0
        deadline = time.monotonic() + timeout_seconds
        delay = 0.01
        while True:
            info = self.pg_info()
            if info is None or info["state"] == "REMOVED":
                raise TrainingFailedError(
                    "placement group removed while waiting for re-mesh"
                )
            if info["state"] == "CREATED" and info["generation"] != self._generation:
                self._generation = info["generation"]
                return info
            if time.monotonic() >= deadline:
                raise TrainingFailedError(
                    f"gang did not re-mesh within {timeout_seconds:.0f}s "
                    f"(state={info['state']}, size={info['size']})"
                )
            time.sleep(delay)
            delay = min(delay * 2, 0.25)

    def request_scale_up(self) -> bool:
        """Ask the head to re-mesh a shrunk gang back to full size."""
        if self._pg is None:
            return False
        from ray_tpu._private.client import client

        return bool(client.pg_reshape(self._pg.id))

    # -- training ---------------------------------------------------------
    def run_training(
        self,
        train_fn: Callable,
        config: Optional[Dict[str, Any]] = None,
        resume_checkpoint: Optional[Checkpoint] = None,
        on_report: Optional[Callable[[int, Dict], None]] = None,
        poll_interval: float = 0.05,
        dataset_shards: Optional[Dict[str, List[Any]]] = None,
    ) -> List[Dict[str, Any]]:
        """Run train_fn on all workers; stream reports; return each rank's
        report list.  Raises TrainingFailedError on any rank failure.

        dataset_shards: {name: [per-rank Dataset shard]} — rank i receives
        shard i under session.get_dataset_shard(name)."""
        with tracing.span("train::executor::run_training", lifecycle=True):
            return self._run_training(
                train_fn, config, resume_checkpoint, on_report, poll_interval, dataset_shards
            )

    def _run_training(self, train_fn, config, resume_checkpoint, on_report, poll_interval, dataset_shards):
        wg = self.worker_group
        assert wg is not None, "call start() first"

        def deliver(polls):
            for i, p in enumerate(polls):
                if self.record is not None:
                    self.record.add_poll(i, p)
                for rep in p["reports"]:
                    all_reports[i].append(rep)
                    if on_report is not None:
                        on_report(i, rep)

        done_refs = [
            w.run_train_fn.remote(
                train_fn,
                config,
                resume_checkpoint,
                {name: shards[i] for name, shards in (dataset_shards or {}).items()},
            )
            for i, w in enumerate(wg.workers)
        ]
        all_reports: List[List[Dict]] = [[] for _ in wg.workers]
        finished = [False] * len(wg.workers)
        error: Optional[BaseException] = None
        last_pg_check = time.monotonic()
        while not all(finished) and error is None:
            time.sleep(poll_interval)
            if self._elastic and time.monotonic() - last_pg_check >= 1.0:
                # Shrunk gang: surface the head's scale-up cue so the
                # trainer can reshape back to full size between steps.
                last_pg_check = time.monotonic()
                info = self.pg_info()
                if (
                    info is not None
                    and info["state"] == "CREATED"
                    and info["scale_up_ready"]
                    and self.num_started_workers < info["orig_size"]
                ):
                    raise RemeshScaleUp(
                        f"gang can scale {info['size']} -> {info['orig_size']}"
                    )
            try:
                polls = ray_tpu.get(
                    [w.poll.remote() for w in wg.workers], timeout=60
                )
            except Exception as e:
                # A dead worker actor (crash/OOM/preemption) must surface as
                # TrainingFailedError so FailureConfig group-restart applies,
                # not as a raw ActorDiedError escaping fit().
                raise TrainingFailedError(
                    f"train worker died during poll: {e}"
                ) from e
            deliver(polls)
            # completion/errors via the run refs (non-blocking check)
            ready, _ = ray_tpu.wait(done_refs, num_returns=len(done_refs), timeout=0)
            for i, r in enumerate(done_refs):
                if r in ready and not finished[i]:
                    try:
                        ray_tpu.get(r, timeout=1)
                        finished[i] = True
                    except Exception as e:
                        error = e
                        break
        if error is not None:
            if self.record is not None:
                # What the workers still hold for the record (the failed train
                # function's own span, its last compiles): an observation,
                # and the failure is raised with or without it.
                try:
                    for i, p in enumerate(ray_tpu.get([w.poll.remote() for w in wg.workers], timeout=5)):
                        self.record.add_poll(i, p)
                except Exception:  # noqa: BLE001
                    pass
            raise TrainingFailedError(str(error)) from error
        # final drain
        try:
            polls = ray_tpu.get([w.poll.remote() for w in wg.workers], timeout=60)
        except Exception as e:
            raise TrainingFailedError(
                f"train worker died during final report drain: {e}"
            ) from e
        deliver(polls)
        return all_reports
