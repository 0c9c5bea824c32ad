"""DataParallelTrainer: N SPMD worker actors run one train function.

ray: python/ray/train/data_parallel_trainer.py:56 (DataParallelTrainer,
training_loop :385) + base_trainer.py:52/:538 (fit).  Simplifications by
design: fit() drives the BackendExecutor directly (the reference wraps every
trainer in a Tune Tuner even for a single run); Tune integration comes via
ray_tpu.tune wrapping the trainer instead — one direction, not a cycle.

Failure model (SURVEY.md §7 hard parts): a rank failure kills the SPMD
program, so FailureConfig.max_failures restarts the WHOLE worker group from
the latest checkpoint — elastic re-mesh, not per-worker restart.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import CheckpointConfig, FailureConfig, RunConfig, ScalingConfig
from ray_tpu.air.result import Result
from ray_tpu.train import run_record
from ray_tpu.train.backend import BackendConfig, JaxConfig
from ray_tpu.train.backend_executor import (
    BackendExecutor,
    RemeshScaleUp,
    TrainingFailedError,
)
from ray_tpu.util import tracing


def _observe_remesh(stages: Dict[str, float]) -> float:
    """Fold one elastic-recovery episode into the remesh_seconds histogram:
    one sample per stage (detect/teardown/replan/respawn/resume) plus the
    end-to-end total, so p50/p99 recovery time is attributable per stage."""
    from ray_tpu._private import telemetry

    h = telemetry.remesh_histogram()
    total = 0.0
    for stage, dur in stages.items():
        d = max(float(dur), 0.0)
        h.observe(d, tags={"stage": stage})
        total += d
    h.observe(total, tags={"stage": "total"})
    return total


class DataParallelTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        backend_config: Optional[BackendConfig] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
        datasets: Optional[Dict[str, Any]] = None,
    ):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config
        self.backend_config = backend_config or JaxConfig()
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint
        # {name: ray_tpu.data.Dataset} — split equally across ranks at fit()
        # (equal row counts: unequal SPMD shards hang compiled collectives),
        # exposed in workers via session.get_dataset_shard(name)
        # (ray: DataParallelTrainer datasets= / session.get_dataset_shard).
        self.datasets = datasets

    def fit(self) -> Result:
        import ray_tpu

        ray_tpu._auto_init()
        # The run's record (train/run_record.py): every lifecycle span under
        # this root shares its trace id, in the workers too, tracing on or off.
        with tracing.span("train::fit", lifecycle=True) as ctx:
            record = run_record.begin(ctx)
            result = self._fit(record)
        result.run_record = record.to_dict()
        return result

    def _fit(self, record: run_record.RunRecord) -> Result:
        failure = self.run_config.failure_config or FailureConfig()
        ckpt_cfg = self.run_config.checkpoint_config or CheckpointConfig()
        attempts_left = failure.max_failures
        latest_ckpt = self.resume_from_checkpoint
        history: list = []
        # History length at the moment of the last checkpoint: on group
        # restart the resumed run re-reports steps after that checkpoint, so
        # anything past this mark belongs to the failed attempt and must be
        # dropped to keep metrics_history free of duplicate steps.
        ckpt_history_len = 0
        last_error: Optional[Exception] = None
        # ONE executor for the whole fit: its placement group is the elastic
        # gang and must survive group restarts (re-mesh respawns workers
        # into the SAME re-planned reservation).
        executor = BackendExecutor(self.backend_config, self.scaling_config, record=record)
        num_workers = self.scaling_config.num_workers
        # In-flight re-mesh episode (stage durations + span context); the
        # "resume" stage closes at the first report of the restarted run.
        remesh: Optional[Dict[str, Any]] = None

        def finalize_remesh():
            nonlocal remesh
            if remesh is None:
                return
            ep, remesh = remesh, None
            mono_now = time.monotonic()
            ep["stages"]["resume"] = mono_now - ep["respawn_end_mono"]
            _observe_remesh(ep["stages"])
            if tracing.is_enabled():
                # Detect started on the head and resume closed inside a
                # report callback — record those (and the parent span whose
                # ids the live teardown/replan/respawn spans parented to)
                # retroactively, mapping monotonic stamps onto the epoch
                # clock for the merged chrome timeline.
                epoch_now = time.time()

                def _at(mono: float) -> float:
                    return epoch_now - (mono_now - mono)

                t0 = ep["t0_mono"]
                tracing.record_span(
                    "train::remesh::detect",
                    _at(t0), _at(t0 + ep["stages"]["detect"]),
                    parent=ep["ctx"],
                )
                tracing.record_span(
                    "train::remesh::resume",
                    _at(ep["respawn_end_mono"]), _at(mono_now),
                    parent=ep["ctx"],
                )
                tracing.record_span(
                    "train::remesh", _at(t0), _at(mono_now), ctx=ep["ctx"],
                    attrs={
                        "direction": ep["direction"],
                        "world_size": executor.num_started_workers,
                        **{
                            f"{k}_s": round(v, 4)
                            for k, v in ep["stages"].items()
                        },
                    },
                )

        def remesh_restart(direction: str, caught_mono: float):
            """One recovery episode: tear down the torn group, wait for the
            head to re-form the gang (shrink: re-planned box at N-1 or a
            replacement host; expand: pg_reshape back to full size), and
            respawn workers into it — measuring each stage."""
            nonlocal num_workers, remesh
            info = executor.pg_info() or {}
            since = info.get("reshaping_since")
            # detect = head noticed the loss -> driver caught the failure
            # (monotonic is system-wide on Linux).  Scale-ups start at the
            # driver: the head only enters RESHAPING after pg_reshape.
            t0 = caught_mono
            if direction == "shrink" and isinstance(since, (int, float)):
                t0 = min(since, caught_mono)
            ctx = {
                "trace_id": os.urandom(16).hex(),
                "span_id": os.urandom(8).hex(),
            }
            stages = {"detect": caught_mono - t0}
            t = time.monotonic()
            with tracing.span("train::remesh::teardown", parent=ctx):
                executor.stop_workers()
            stages["teardown"] = time.monotonic() - t
            t = time.monotonic()
            with tracing.span("train::remesh::replan", parent=ctx):
                if direction == "expand":
                    executor.request_scale_up()
                new_info = executor.wait_remesh()
            stages["replan"] = time.monotonic() - t
            t = time.monotonic()
            with tracing.span("train::remesh::respawn", parent=ctx):
                executor.start(num_workers=new_info["size"])
                num_workers = executor.num_started_workers
            end = time.monotonic()
            stages["respawn"] = end - t
            remesh = {
                "stages": stages, "ctx": ctx, "t0_mono": t0,
                "respawn_end_mono": end, "direction": direction,
            }

        def on_report(rank: int, rep: Dict):
            nonlocal latest_ckpt, ckpt_history_len
            finalize_remesh()  # first report after a re-mesh: resume done
            if rank == 0:
                history.append(rep["metrics"])
                # Inside a tune trial actor: stream rank-0 reports up
                # to the trial session so ASHA/PBT see intermediate
                # results (ray: base_trainer.py:538 wraps trainers in
                # trainables for the same effect).
                from ray_tpu.train import session as _sess

                if _sess._session is not None:
                    _sess._session.report(
                        rep["metrics"], checkpoint=rep.get("checkpoint")
                    )
            if rep.get("checkpoint") is not None:
                latest_ckpt = rep["checkpoint"]
                ckpt_history_len = len(history)

        try:
            while True:
                try:
                    executor.start(num_workers=num_workers)
                    num_workers = executor.num_started_workers

                    shards = None
                    if self.datasets:
                        # Split by the ACTUAL world size: a shrunk elastic
                        # gang re-splits so every row is still covered.
                        n = executor.num_started_workers or num_workers
                        shards = {
                            name: ds.split(n, equal=True)
                            for name, ds in self.datasets.items()
                        }
                    reports = executor.run_training(
                        self.train_loop_per_worker,
                        config=self.train_loop_config,
                        resume_checkpoint=latest_ckpt,
                        on_report=on_report,
                        dataset_shards=shards,
                    )
                    finalize_remesh()  # run ended before reporting again
                    metrics = history[-1] if history else {}
                    return Result(
                        metrics=metrics,
                        checkpoint=latest_ckpt,
                        metrics_history=history,
                    )
                except (RemeshScaleUp, TrainingFailedError) as e:
                    caught = time.monotonic()
                    is_remesh = isinstance(e, RemeshScaleUp) or (
                        executor.remesh_in_progress()
                    )
                    if is_remesh:
                        # Elastic re-mesh is recovery, not failure: restart
                        # from the latest checkpoint WITHOUT charging the
                        # failure budget.
                        direction = (
                            "expand" if isinstance(e, RemeshScaleUp)
                            else "shrink"
                        )
                        try:
                            remesh_restart(direction, caught)
                            del history[ckpt_history_len:]
                            continue
                        except TrainingFailedError as e2:
                            e = e2  # re-mesh itself failed: charge budget
                    if isinstance(e, RemeshScaleUp):  # restart failed above
                        e = TrainingFailedError(str(e))
                    last_error = e
                    if attempts_left == 0:
                        return Result(
                            metrics=history[-1] if history else None,
                            checkpoint=latest_ckpt,
                            error=e,
                            metrics_history=history,
                        )
                    if attempts_left > 0:
                        attempts_left -= 1
                    # group restart from latest checkpoint; drop the failed
                    # attempt's post-checkpoint metrics
                    executor.stop_workers()
                    del history[ckpt_history_len:]
        finally:
            executor.shutdown()


class JaxTrainer(DataParallelTrainer):
    """Sugar: DataParallelTrainer with the SPMD mesh backend preconfigured.

    The TPU-native answer to the reference's TorchTrainer
    (ray: python/ray/train/torch/torch_trainer.py): instead of wrapping the
    model in DDP, the train loop builds a global mesh (jax.devices() spans
    every worker after backend setup) and pjits its step.
    """

    def __init__(self, train_loop_per_worker: Callable, **kwargs):
        kwargs.setdefault("backend_config", JaxConfig())
        super().__init__(train_loop_per_worker, **kwargs)
