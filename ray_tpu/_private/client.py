"""Core client: routes API calls to the driver Runtime or, inside a worker
process, over the control connection to the owner.

Mirrors the split in the reference where both drivers and workers link the
same CoreWorker library (ray: src/ray/core_worker/core_worker_process.h) and
the Python API is mode-agnostic (ray: python/ray/_private/worker.py:404).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import serialization as ser
from ray_tpu._private.refs import ObjectRef
from ray_tpu._private.task_spec import TaskSpec
from ray_tpu.exceptions import GetTimeoutError


def in_worker() -> bool:
    from ray_tpu._private import worker_proc

    return worker_proc.get_worker_runtime() is not None


def current_session() -> Optional[str]:
    """Session name of the active runtime (None if not initialized).

    Used to invalidate per-process caches (exported functions) across
    init/shutdown cycles, like the reference's per-job function table
    (ray: python/ray/_private/function_manager.py keyed by job id).
    """
    from ray_tpu._private import runtime as rt
    from ray_tpu._private.worker_proc import get_worker_runtime

    wr = get_worker_runtime()
    if wr is not None:
        return wr.session_name
    if rt.is_initialized():
        return rt.get_runtime().session_name
    return None


class CoreClient:
    """Facade over either the in-process Runtime (driver) or the worker's
    connection to it."""

    # -- driver/worker dispatch ---------------------------------------------

    def _rt(self):
        from ray_tpu._private.runtime import get_runtime

        return get_runtime()

    def _wr(self):
        from ray_tpu._private.worker_proc import get_worker_runtime

        return get_worker_runtime()

    # -- functions ----------------------------------------------------------

    def export_function(self, fn_id: str, blob: bytes) -> None:
        wr = self._wr()
        if wr is not None:
            wr.request("export_function", (fn_id, blob))
        else:
            self._rt().state.export_function(fn_id, blob)

    # -- tasks ---------------------------------------------------------------

    @staticmethod
    def _stamp_parent(spec: TaskSpec) -> None:
        from ray_tpu._private.worker_proc import current_task_id

        if spec.parent_task_id is None:
            spec.parent_task_id = current_task_id()
        from ray_tpu.util import tracing

        if spec.trace_ctx is not None:
            return
        if tracing.is_enabled():
            # The submit span's context rides the spec, so the executor's
            # run span parents to it across the process boundary.
            with tracing.span(
                f"submit::{spec.name}", attrs={"task_id": spec.task_id}
            ) as ctx:
                spec.trace_ctx = dict(ctx)
        else:
            # Tracing off: there is an ambient context only inside a
            # lifecycle span (a fit()), whose spans on the executor's side
            # parent to it across the hop; no span is recorded for the task.
            spec.trace_ctx = tracing.current_context()

    def submit(self, spec: TaskSpec) -> List[ObjectRef]:
        self._stamp_parent(spec)
        wr = self._wr()
        if wr is not None:
            wr.note_escaped(spec.contained_refs)
            # Nested submissions push straight to a head-leased worker when
            # the task shape allows it (ray: direct_task_transport.h:75);
            # a denied/ineligible lease falls back to the queued head path.
            if wr.direct is not None:
                return_ids = wr.direct.submit_plain(spec)
                if return_ids is not None:
                    return [ObjectRef(oid, _count=False) for oid in return_ids]
            return_ids = wr.request("submit", spec)
        else:
            return_ids = self._rt().submit_task(spec)
        return [ObjectRef(oid) for oid in return_ids]

    def create_actor(self, spec: TaskSpec) -> str:
        self._stamp_parent(spec)
        wr = self._wr()
        if wr is not None:
            wr.note_escaped(spec.contained_refs)
            return wr.request("create_actor", spec)
        return self._rt().create_actor(spec)

    def submit_actor_task(self, spec: TaskSpec) -> List[ObjectRef]:
        self._stamp_parent(spec)
        wr = self._wr()
        if wr is not None:
            # Hot path: push straight to the actor's worker when eligible
            # (ray: direct_actor_task_submitter.h:67) — zero head messages.
            wr.note_escaped(spec.contained_refs)
            if wr.direct is not None:
                return_ids = wr.direct.submit(spec)
                if return_ids is not None:
                    # _count=False: the transport pre-counted these refs at
                    # submit (see DirectTransport.submit).
                    return [ObjectRef(oid, _count=False) for oid in return_ids]
            return_ids = wr.request("actor_call", spec)
        else:
            return_ids = self._rt().submit_actor_task(spec)
        return [ObjectRef(oid) for oid in return_ids]

    # -- objects -------------------------------------------------------------

    def put(self, value: Any) -> ObjectRef:
        wr = self._wr()
        if wr is not None:
            oid = wr.put_value(value)
            return ObjectRef(oid)
        return self._rt().put(value)

    def get(self, refs, timeout: Optional[float] = None):
        wr = self._wr()
        if wr is None:
            return self._rt().get(refs, timeout)
        single = isinstance(refs, ObjectRef)
        if single:
            refs = [refs]
        values = []
        deadline = None if timeout is None else time.monotonic() + timeout
        for r in refs:
            t = None if deadline is None else max(0.0, deadline - time.monotonic())
            try:
                values.append(self._worker_get_one(wr, r.id, t))
            except Exception:
                raise
        return values[0] if single else values

    def _worker_get_one(self, wr, oid: str, timeout: Optional[float]):
        # One resolution path for arg resolution AND user-level get: local
        # node store, then the owner, which replies inline / local-shm /
        # pull-endpoints (cross-node transfer).
        return wr.get_value(oid, timeout=timeout)

    def wait(self, refs, num_returns=1, timeout=None, fetch_local=True):
        wr = self._wr()
        if wr is None:
            return self._rt().wait_refs(refs, num_returns, timeout)
        import queue as _q

        # Event-driven: the owner parks each request until num_returns are
        # ready (or its chunk timer lapses) and replies once — no poll loop.
        # Chunking (30s server-side timers + a transport guard) bounds the
        # damage of a lost reply: the next chunk re-asks instead of hanging.
        deadline = None if timeout is None else time.monotonic() + timeout
        oids = [r.id for r in refs]
        # Locally-owned direct results aren't visible to the owner until
        # promoted: promote any involved in a wait so one head-side wait
        # covers the whole list (wait is not the per-call hot path).
        wr.note_escaped([oid for oid in oids if wr.direct is not None
                         and wr.direct.owns(oid)])
        flags = [False] * len(refs)
        while True:
            remaining = None if deadline is None else deadline - time.monotonic()
            chunk = 30.0 if remaining is None else max(min(remaining, 30.0), 0.0)
            try:
                flags = wr.request(
                    "wait_objects", (oids, num_returns, chunk), timeout=chunk + 10
                )
            except _q.Empty:
                pass  # lost reply: fall through and re-ask (or give up)
            if sum(flags) >= num_returns:
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
        ready = [r for r, f in zip(refs, flags) if f]
        ready = ready[:num_returns] if len(ready) >= num_returns else ready
        ready_set = {r.id for r in ready}
        not_ready = [r for r in refs if r.id not in ready_set]
        return ready, not_ready

    def cancel(self, ref: ObjectRef, force: bool = False) -> None:
        wr = self._wr()
        if wr is not None:
            # Direct calls are tracked caller-side only: cancel rides the
            # peer socket with queued-drop semantics.  force=True is
            # deliberately NOT escalated here — the reference likewise
            # rejects force-cancellation of actor tasks (the interruption
            # primitive for a stuck actor is kill, not cancel).
            if wr.direct is not None and wr.direct.cancel(ref.id):
                return
            wr.request("cancel", (ref.id, force))
        else:
            self._rt().cancel(ref, force)

    # -- actors --------------------------------------------------------------

    def kill_actor(self, actor_id: str, no_restart: bool = True) -> None:
        wr = self._wr()
        if wr is not None:
            wr.request("kill_actor", (actor_id, no_restart))
        else:
            self._rt().kill_actor(actor_id, no_restart)

    def get_named_actor(
        self, name: str, namespace: Optional[str]
    ) -> Tuple[str, List[str], int]:
        """(actor_id, method_names, actor_max_concurrency)."""
        wr = self._wr()
        if wr is not None:
            return wr.request("get_actor_named", (name, namespace))
        rt = self._rt()
        return rt._handle_req("driver", -1, "get_actor_named", (name, namespace))

    # -- kv ------------------------------------------------------------------

    def kv_put(self, key: str, value: bytes, namespace: str = "") -> None:
        wr = self._wr()
        if wr is not None:
            wr.request("kv_put", (key, value, namespace))
        else:
            self._rt().state.kv_put(key, value, namespace)

    def kv_get(self, key: str, namespace: str = "") -> Optional[bytes]:
        wr = self._wr()
        if wr is not None:
            return wr.request("kv_get", (key, namespace))
        return self._rt().state.kv_get(key, namespace)

    # -- placement groups ----------------------------------------------------

    def pg_create(self, bundles, strategy, name=None) -> str:
        wr = self._wr()
        if wr is not None:
            # Mint the id CLIENT-side: a request retried across a head
            # bounce then dedupes instead of double-reserving bundles.
            from ray_tpu._private import ids as _ids

            pg_id = _ids.placement_group_id()
            return wr.request("pg_create", (bundles, strategy, name, pg_id))
        return self._rt().create_placement_group(bundles, strategy, name).pg_id

    def pg_state(self, pg_id: str) -> Optional[str]:
        wr = self._wr()
        if wr is not None:
            return wr.request("pg_state", pg_id)
        pg = self._rt().state.placement_groups.get(pg_id)
        return pg.state if pg else None

    def pg_remove(self, pg_id: str) -> None:
        wr = self._wr()
        if wr is not None:
            wr.request("pg_remove", pg_id)
        else:
            self._rt().remove_placement_group(pg_id)

    def pg_info(self, pg_id: str) -> Optional[Dict]:
        """Elastic-gang introspection: state + generation + shrunk size +
        scale-up cue (see Runtime.pg_info)."""
        wr = self._wr()
        if wr is not None:
            return wr.request("pg_info", pg_id)
        return self._rt().pg_info(pg_id)

    def pg_reshape(self, pg_id: str) -> bool:
        """Ask the head to re-mesh a shrunk MESH gang back to full size."""
        wr = self._wr()
        if wr is not None:
            return bool(wr.request("pg_reshape", pg_id))
        return self._rt().pg_reshape(pg_id)

    # -- cluster -------------------------------------------------------------

    def cluster_resources(self) -> Dict[str, float]:
        wr = self._wr()
        if wr is not None:
            return wr.request("cluster_resources", None)
        return self._rt().cluster_resources()

    def available_resources(self) -> Dict[str, float]:
        wr = self._wr()
        if wr is not None:
            return wr.request("available_resources", None)
        return self._rt().available_resources()


client = CoreClient()


_EMPTY_ARGS_BLOB = None


def build_args_blob(args: tuple, kwargs: dict):
    """Serialize call args; returns (packed_blob, contained_ids, top_level_dep_ids)."""
    global _EMPTY_ARGS_BLOB
    if not args and not kwargs:
        # No-arg calls (fan-outs of nullary tasks are a whole bench shape)
        # share one immutable pre-packed blob instead of re-serializing
        # ((), {}) per call.
        blob = _EMPTY_ARGS_BLOB
        if blob is None:
            payload, buffers, _ = ser.serialize(((), {}))
            blob = _EMPTY_ARGS_BLOB = bytes(ser.pack(payload, buffers))
        return blob, [], []
    payload, buffers, contained = ser.serialize((args, kwargs))
    deps = [a.id for a in args if isinstance(a, ObjectRef)]
    deps += [v.id for v in kwargs.values() if isinstance(v, ObjectRef)]
    return bytes(ser.pack(payload, buffers)), contained, deps
