"""Train backends: per-framework worker-group setup.

ray: python/ray/train/backend.py (Backend/BackendConfig) and
train/torch/config.py:69 (_setup_torch_process_group — rank-0 address
broadcast, then dist.init_process_group :113).  TPU-native: the process
group IS the XLA runtime — JaxConfig's on_start picks a coordinator on rank
0 and every worker calls jax.distributed.initialize, after which one pjit
program spans all workers' chips over ICI/DCN.  No NCCL library, no wrapper:
collectives are compiled (SURVEY.md §5.8).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ray_tpu.train.worker_group import WorkerGroup


@dataclasses.dataclass
class BackendConfig:
    """Base backend config (ray: python/ray/train/backend.py)."""

    def backend_cls(self):
        return Backend


class Backend:
    """Framework setup/teardown hooks around a WorkerGroup."""

    def on_start(self, worker_group: WorkerGroup, backend_config: "BackendConfig"):
        pass

    def on_shutdown(self, worker_group: WorkerGroup, backend_config: "BackendConfig"):
        pass


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """SPMD mesh bootstrap over the worker group.

    coordinator_port 0 = pick a free port on rank 0's host.
    platform: force a jax platform in workers.  Tests use "cpu"; a chip run
    passes "tpu", which makes a missing chip an error at worker start.  With
    None, jax picks, and without a chip it falls back to CPU with a warning.
    """

    coordinator_port: int = 0
    platform: Optional[str] = None

    def backend_cls(self):
        return _JaxBackend


def _import_jax(platform: Optional[str]):
    """`import jax` as the lifecycle span `train::backend::import_jax`, in
    whichever call reaches it first in this worker (seconds in a fresh one,
    nothing after), and the run record's compile listener with it."""
    import os
    import sys

    from ray_tpu.train import run_record
    from ray_tpu.util import tracing

    if platform:
        os.environ["JAX_PLATFORMS"] = platform
    with tracing.span("train::backend::import_jax", lifecycle=True,
                      attrs={"already_imported": "jax" in sys.modules}):
        import jax
    run_record.install_jax_listener()
    return jax


def _pick_coordinator(port: int, platform: Optional[str] = None) -> str:
    # `ray_tpu.parallel` imports jax: rank 0 pays its import here, not later.
    _import_jax(platform)
    from ray_tpu.parallel.bootstrap import pick_coordinator_address

    return pick_coordinator_address(port)


def _wait_for_chips(timeout_s: float = 60.0, pattern: str = "/dev/vfio/[0-9]*", opener=None) -> float:
    """Block until the chips this host hands out (`/dev/vfio/<group>`) can be
    opened; returns the seconds waited.  A process that held them releases
    them while it EXITS (a reset per chip, its pinned host memory): seconds
    on a four-chip host, during which libtpu's open gets EBUSY and fails the
    whole backend ("Couldn't open iommu group").  A job that starts right
    after another one ended must outwait that, not die of it.  Opening and
    closing a group file claims nothing.  Anything but EBUSY is left for
    libtpu to report.  (`pattern` and `opener` are the test's handles.)"""
    import errno
    import glob
    import os
    import time

    opener = opener or os.open
    start = time.monotonic()
    for path in glob.glob(pattern):
        while True:
            try:
                os.close(opener(path, os.O_RDWR))
            except OSError as e:
                if e.errno == errno.EBUSY and time.monotonic() - start < timeout_s:
                    time.sleep(0.25)
                    continue
            break
    return time.monotonic() - start


def _init_jax_distributed(coordinator: str, world_size: int, rank: int, platform):
    from ray_tpu.train import run_record
    from ray_tpu.util import tracing

    # What every chip worker pays before its loop's first line, by name:
    # lifecycle spans, in every run's record (train/run_record.py).
    jax = _import_jax(platform)
    if platform:
        jax.config.update("jax_platforms", platform)
    if world_size > 1:
        with tracing.span("train::backend::distributed_init", lifecycle=True):
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world_size,
                process_id=rank,
            )
    if platform == "tpu":
        attrs = {}
        with tracing.span("train::backend::chip_wait", attrs=attrs, lifecycle=True):
            attrs["waited_s"] = _wait_for_chips()
        run_record.counters()["chip_wait"].inc(attrs["waited_s"])
    with tracing.span("train::backend::device_open", lifecycle=True):
        global_devices = len(jax.devices())
    return {
        "rank": rank,
        "global_devices": global_devices,
        "local_devices": jax.local_device_count(),
    }


class _JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        # One worker is its own coordinator: nothing to pick.
        coordinator = "" if worker_group.num_workers == 1 else worker_group.execute_single(
            0, _pick_coordinator, backend_config.coordinator_port, backend_config.platform, timeout=60
        )
        # All workers join the XLA coordination service (the analogue of the
        # reference broadcasting rank-0's addr then init_process_group).
        return self._start_all(worker_group, coordinator, backend_config)

    @staticmethod
    def _start_all(worker_group: WorkerGroup, coordinator: str, cfg: JaxConfig):
        import ray_tpu

        n = worker_group.num_workers
        refs = [
            w.run_fn.remote(_init_jax_distributed, coordinator, n, i, cfg.platform)
            for i, w in enumerate(worker_group.workers)
        ]
        return ray_tpu.get(refs, timeout=300)

    def on_shutdown(self, worker_group: WorkerGroup, backend_config: JaxConfig):
        def _shut():
            import jax

            try:
                jax.distributed.shutdown()
            except Exception:
                pass

        try:
            worker_group.execute(_shut, timeout=30)
        except Exception:
            pass
