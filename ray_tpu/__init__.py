"""ray_tpu: a TPU-native distributed ML framework.

Public API mirrors the reference's surface
(ray: python/ray/_private/worker.py -- init :1043, shutdown :1600,
 get :2263, put :2410, wait :2472, kill :2629 area, remote :2629) while the
implementation is built TPU-first (see SURVEY.md section 7): JAX/XLA programs over
device meshes do the compute; this runtime schedules host processes, owns
objects, and orchestrates multi-host SPMD.

Importing ray_tpu must stay light: no jax import happens until you touch
ray_tpu.parallel / models / train / ops.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from ray_tpu import exceptions
from ray_tpu._private.client import client
from ray_tpu._private.refs import ObjectRef
from ray_tpu.actor import ActorClass, ActorHandle, exit_actor, get_actor
from ray_tpu.remote_function import RemoteFunction, remote

__version__ = "0.1.0"

__all__ = [
    "init",
    "shutdown",
    "is_initialized",
    "remote",
    "get",
    "put",
    "wait",
    "kill",
    "cancel",
    "get_actor",
    "exit_actor",
    "ObjectRef",
    "ActorClass",
    "ActorHandle",
    "RemoteFunction",
    "available_resources",
    "cluster_resources",
    "exceptions",
    "nodes",
]


def init(
    num_cpus: Optional[int] = None,
    resources: Optional[Dict[str, float]] = None,
    namespace: str = "default",
    ignore_reinit_error: bool = False,
    _system_config: Optional[Dict[str, Any]] = None,
    address: Optional[str] = None,
    _authkey: Optional[str] = None,
    log_to_driver: bool = True,
    **_unused,
):
    """Start the per-host runtime (driver mode), or ATTACH to a standalone
    head process when `address` is given (head-split mode — the analogue of
    ray.init(address=...) / the Ray Client ray:// attach).

    address: path to a head.json / its session dir (written by
    `python -m ray_tpu._private.head`), or "host:port" with `_authkey`.
    An attached driver can die (even kill -9) without taking the cluster
    down; detached actors keep serving and a new driver can re-attach.

    Inside a worker process this is a no-op (the worker is already connected),
    matching the reference's behavior for nested init.

    _system_config: programmatic overrides of the runtime knob table
    (ray: ray.init(_system_config=...); see _private/config.py for the
    knobs — env form is RAY_TPU_<NAME>).  Applied driver-side; workers read
    the env forms they inherit.
    """
    from ray_tpu._private import runtime as rt
    from ray_tpu._private.worker_proc import get_worker_runtime

    if get_worker_runtime() is not None:
        return
    if rt.is_initialized():
        if ignore_reinit_error:
            return
        raise RuntimeError("ray_tpu.init() called twice (pass ignore_reinit_error=True)")
    if _system_config:
        from ray_tpu._private import config as _cfg

        _cfg.set_system_config(_system_config)
    if address is not None:
        from ray_tpu._private import driver_client

        driver_client.attach(
            address, authkey=_authkey, namespace=namespace,
            log_to_driver=log_to_driver,
        )
        return
    from ray_tpu.util import tracing

    # A lifecycle span (util/tracing.py): recorded whether tracing is on or
    # not, and `runtime::shutdown` joins its trace.
    with tracing.span("runtime::init", lifecycle=True) as ctx:
        runtime = rt.init_runtime(
            num_cpus=num_cpus, resources=resources, namespace=namespace
        )
    runtime.trace_id = ctx["trace_id"]
    # Honor the flag in LOCAL driver mode too (the runtime's default comes
    # from the log_to_driver config knob).
    runtime.log_to_driver = bool(log_to_driver) and runtime.log_to_driver


def shutdown():
    from ray_tpu._private import driver_client
    from ray_tpu._private import runtime as rt

    if driver_client.is_attached():
        driver_client.detach()
        return
    rt.shutdown_runtime()


def is_initialized() -> bool:
    from ray_tpu._private import runtime as rt
    from ray_tpu._private.worker_proc import get_worker_runtime

    return rt.is_initialized() or get_worker_runtime() is not None


def _auto_init():
    from ray_tpu._private import runtime as rt
    from ray_tpu._private.worker_proc import get_worker_runtime

    if not rt.is_initialized() and get_worker_runtime() is None:
        init()


def get(refs, *, timeout: Optional[float] = None):
    _auto_init()
    return client.get(refs, timeout)


def put(value: Any) -> ObjectRef:
    _auto_init()
    return client.put(value)


def wait(refs, *, num_returns: int = 1, timeout: Optional[float] = None, fetch_local=True):
    _auto_init()
    if not isinstance(refs, list):
        raise TypeError("ray_tpu.wait() expects a list of ObjectRefs")
    if num_returns > len(refs):
        raise ValueError("num_returns exceeds number of refs")
    return client.wait(refs, num_returns, timeout, fetch_local)


def kill(actor: ActorHandle, *, no_restart: bool = True):
    client.kill_actor(actor._id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False):
    client.cancel(ref, force)


def available_resources() -> Dict[str, float]:
    _auto_init()
    return client.available_resources()


def cluster_resources() -> Dict[str, float]:
    _auto_init()
    return client.cluster_resources()


def nodes():
    """List cluster nodes (ray: ray.nodes())."""
    from ray_tpu._private.runtime import get_runtime

    rt = get_runtime()
    return [
        {
            "NodeID": n.node_id,
            "Alive": n.alive,
            "Resources": dict(n.resources),
            "Available": dict(n.available),
            "IsHead": n.is_head,
        }
        for n in rt.state.nodes.values()
    ]
