"""Distributed tracing: spans with their context in task specs.

ray: python/ray/util/tracing/tracing_helper.py — the reference wraps
remote calls in spans and propagates the context INSIDE the task spec
(`_DictPropagator.inject_current_context`, :160), so a task's execute span
parents to its submitter's span across processes.  Same design here:

  * two classes of span, one mechanism.  Per-task and per-step spans are
    opt-in (`RAY_TPU_TRACE=1` or `enable_tracing()`), zero overhead off.
    LIFECYCLE spans (`lifecycle=True`: a job's start, a compile, a
    shutdown; a few dozen per run, none on a hot path) are recorded
    always, and besides the buffer land in a bounded per-process store
    that `lifecycle_spans()` reads, from which `ray_tpu.train` builds a
    run's record;
  * the ACTIVE trace context lives in a contextvar; submission injects it
    into `spec.trace_ctx` as a W3C-traceparent-style dict, execution
    adopts it, so nested submits chain naturally;
  * spans record to an in-process buffer that workers flush to the head
    (state API / timeline);
  * SCOPES are not spans: `scope(name)` names a region of a function jax
    traces (`jax.named_scope` plus a per-thread table of self seconds and
    entries by path), and the table rides as one attribute on the
    `jax::trace` span that covers it (`train/run_record.py`).
"""

from __future__ import annotations

import contextvars
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Deque, Dict, List, Optional, Tuple

_enabled = os.environ.get("RAY_TPU_TRACE", "") not in ("", "0")
_current: "contextvars.ContextVar[Optional[Dict[str, str]]]" = contextvars.ContextVar(
    "raytpu_trace_ctx", default=None
)
_buffer_lock = threading.Lock()
_buffer: Deque[Dict[str, Any]] = deque(maxlen=10000)
# Every lifecycle span of this process, newest last: not drained by the
# flush to the head, so a run's record can still be put together after
# `ray_tpu.shutdown()`.
_lifecycle: Deque[Dict[str, Any]] = deque(maxlen=4096)
_lifecycle_total = 0


def enable_tracing() -> None:
    """Turn span recording on for this process (children inherit via the
    RAY_TPU_TRACE env var when set instead)."""
    global _enabled
    _enabled = True


def disable_tracing() -> None:
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def current_context() -> Optional[Dict[str, str]]:
    """The ambient trace context of this thread, or None.  With tracing off
    there is one only inside a lifecycle span (or a task that adopted one),
    which is when submission still injects it into `spec.trace_ctx`."""
    return _current.get()


@contextmanager
def adopt(ctx: Optional[Dict[str, str]]):
    """Make `ctx` (a spec's trace_ctx) ambient without recording a span:
    how a task run with tracing off still parents the lifecycle spans
    opened inside it to its submitter's."""
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


def _keep(rec: Dict[str, Any], lifecycle: bool) -> None:
    global _lifecycle_total
    with _buffer_lock:
        _buffer.append(rec)
        if lifecycle:
            _lifecycle.append(rec)
            _lifecycle_total += 1


@contextmanager
def span(name: str, parent: Optional[Dict[str, str]] = None,
         attrs: Optional[Dict[str, Any]] = None, lifecycle: bool = False):
    """Record one span.  `parent` (e.g. a spec's trace_ctx) wins over the
    ambient context; the new span becomes ambient for the duration, so
    anything submitted inside parents to it.  Yields the span's context;
    a key added to `attrs` inside the block is recorded with it."""
    if not (_enabled or lifecycle):
        yield None
        return
    up = parent if parent is not None else _current.get()
    ctx = {
        "trace_id": (up or {}).get("trace_id") or _new_id(16),
        "span_id": _new_id(8),
    }
    rec = {
        "name": name,
        "trace_id": ctx["trace_id"],
        "span_id": ctx["span_id"],
        "parent_span_id": (up or {}).get("span_id"),
        "start": time.time(),
        "attrs": attrs if attrs is not None else {},
        "pid": os.getpid(),
    }
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)
        rec["end"] = time.time()
        rec["attrs"] = dict(rec["attrs"])
        _keep(rec, lifecycle)
        # Completed spans also land in the process's flight-recorder ring
        # (telemetry.py): a crash dump shows what this process was doing
        # in its last seconds, span by span.
        try:
            from ray_tpu._private import telemetry as _telemetry

            _telemetry.note(
                "span",
                name=rec["name"],
                span_id=rec["span_id"],
                dur_ms=round((rec["end"] - rec["start"]) * 1000, 3),
            )
        except Exception:
            pass


def record_span(
    name: str,
    start: float,
    end: float,
    parent: Optional[Dict[str, str]] = None,
    attrs: Optional[Dict[str, Any]] = None,
    ctx: Optional[Dict[str, str]] = None,
    lifecycle: bool = False,
) -> Optional[Dict[str, str]]:
    """Record an ALREADY-FINISHED span with explicit epoch timestamps.

    For intervals whose boundaries are only known after the fact — e.g.
    the "detect" stage of an elastic re-mesh starts on the head before the
    driver notices, and "resume" ends inside a report callback.  `ctx`
    pins the span's own ids so sibling spans recorded earlier can already
    have parented to it; returns the span's context for further chaining.
    """
    if not (_enabled or lifecycle):
        return None
    c = {
        "trace_id": (ctx or parent or {}).get("trace_id") or _new_id(16),
        "span_id": (ctx or {}).get("span_id") or _new_id(8),
    }
    _keep({
        "name": name,
        "trace_id": c["trace_id"],
        "span_id": c["span_id"],
        "parent_span_id": (parent or {}).get("span_id"),
        "start": start,
        "end": end,
        "attrs": dict(attrs or {}),
        "pid": os.getpid(),
    }, lifecycle)
    return c


@contextmanager
def annotate(name: str, lifecycle: bool = False):
    """A span of the PROGRAM's own host work on the profiler's clock.

    Opens a `jax.profiler.TraceAnnotation` if and only if jax is already
    imported (this module never imports it: the driver and the head stay
    off jax), so under a profiler session the span lands on `/host:CPU`
    next to the device planes; with tracing enabled (or `lifecycle`) the
    same interval is also recorded through `record_span`, so `ray_tpu
    timeline` shows it.  With neither on it costs the annotation's one
    `TraceMe` check."""
    jax = sys.modules.get("jax")
    on = _enabled or lifecycle
    start = time.time() if on else 0.0
    try:
        if jax is None:
            yield
        else:
            with jax.profiler.TraceAnnotation(name):
                yield
    finally:
        if on:
            record_span(name, start, time.time(), parent=_current.get(), lifecycle=lifecycle)


# -- scopes: the names the program gives its regions while jax traces it ----------

_clock = time.time  # the clock of jax's own trace events, so a block can be placed inside one
_BLOCKS_KEPT = 256  # closed outermost blocks a thread keeps until a span takes them
_scopes = threading.local()


class _ScopeState:
    """One thread's open scopes and the outermost blocks it has closed."""

    __slots__ = ("stack", "table", "kernels", "closed")

    def __init__(self):
        self.stack: List[list] = []  # [path, start, seconds under children], outermost first
        self.table: Dict[str, List[Any]] = {}  # path -> [self seconds, entries], of the block that is open
        self.kernels: set = set()
        self.closed: Deque[tuple] = deque(maxlen=_BLOCKS_KEPT)  # (start, end, table, kernels), oldest first


class scope:
    """`with tracing.scope(name):` is how the program names a region.

    It enters `jax.named_scope(name)` if and only if jax is already imported
    (taken from `sys.modules`, as `annotate` does), so the ops traced inside
    carry the name to the device, and it accounts for the block on the
    calling thread: a table `path -> [self seconds, entries]`, `path` the
    open scopes' names joined by `/`, self seconds the block's less its
    children's.  Python runs the block only while jax TRACES the function
    around it; a compiled step never enters it, so the cost is the tracing's:
    two clock reads and a dictionary update an entry.

    `host_only=True` accounts and opens no `named_scope` (no op's metadata
    changes): for a region that must have a name on the host and none in the
    HLO.  `kernel=True` says the name is a kernel's own (the scope around a
    `pallas_call`), for the readers that tell kernels' bodies from the rest.

    When the outermost scope of a thread closes, its table is kept as one
    block; `take_scopes` hands the blocks inside an interval to the span that
    covers them (`train/run_record.py`: the outermost `jax::trace`)."""

    __slots__ = ("name", "kernel", "host_only", "_named")

    def __init__(self, name: str, *, kernel: bool = False, host_only: bool = False):
        self.name = name
        self.kernel = kernel
        self.host_only = host_only
        self._named = None

    def __enter__(self) -> None:
        start = _clock()
        jax = None if self.host_only else sys.modules.get("jax")
        if jax is not None:
            self._named = jax.named_scope(self.name)
            self._named.__enter__()
        state = getattr(_scopes, "state", None)
        if state is None:
            state = _scopes.state = _ScopeState()
        stack = state.stack
        stack.append([stack[-1][0] + "/" + self.name if stack else self.name, start, 0.0])
        if self.kernel:
            state.kernels.add(self.name)

    def __exit__(self, *exc) -> None:
        try:
            if self._named is not None:
                named, self._named = self._named, None
                named.__exit__(*exc)
        finally:
            state = _scopes.state
            path, start, under = state.stack.pop()
            end = _clock()
            row = state.table.get(path)
            if row is None:
                state.table[path] = [end - start - under, 1]
            else:
                row[0] += end - start - under
                row[1] += 1
            if state.stack:
                state.stack[-1][2] += end - start
            else:
                state.closed.append((start, end, state.table, state.kernels))
                state.table, state.kernels = {}, set()


def open_scope() -> Optional[str]:
    """The name of the innermost scope open on the calling thread, or None:
    for code that starts a name stack of its own under its caller's name (the
    body of a `jax.shard_map`) and wants its ops found under that name still."""
    stack = getattr(getattr(_scopes, "state", None), "stack", None)
    if not stack:
        return None
    return stack[-1][0][len(stack[-2][0]) + 1:] if len(stack) > 1 else stack[-1][0]


def take_scopes(start: float, end: float) -> Optional[Tuple[Dict[str, List[Any]], List[str]]]:
    """(table, kernel names) of what the calling thread's scopes accrued in
    blocks that lie inside [start, end], summed, or None if there is none.
    Blocks that ended before `start` belong to no span (an eager call) and
    are dropped; later ones stay for the span that covers them."""
    state = getattr(_scopes, "state", None)
    if state is None:
        return None
    table: Dict[str, List[Any]] = {}
    kernels: set = set()
    closed = state.closed
    while closed and closed[0][0] < end:
        b_start, b_end, rows, names = closed.popleft()
        if b_start < start or b_end > end:
            continue
        kernels |= names
        for path, (self_s, entries) in rows.items():
            row = table.setdefault(path, [0.0, 0])
            row[0] += self_s
            row[1] += entries
    return (table, sorted(kernels)) if table else None


def drain_spans() -> List[Dict[str, Any]]:
    """Take the buffered spans (worker flush loops ship them to the head)."""
    with _buffer_lock:
        out = list(_buffer)
        _buffer.clear()
    return out


def lifecycle_count() -> int:
    """How many lifecycle spans this process has recorded so far."""
    return _lifecycle_total


def lifecycle_spans(trace_id: Optional[str] = None, since: int = 0) -> List[Dict[str, Any]]:
    """This process's lifecycle spans (of one trace, if given), oldest
    first, recorded after the first `since` of them: a poller passes the
    `lifecycle_count()` it last saw.  Not drained: bounded, the oldest
    fall out."""
    with _buffer_lock:
        kept = list(_lifecycle)
        first = _lifecycle_total - len(kept)  # the count before the oldest one kept
    return [s for s in kept[max(since - first, 0):] if trace_id is None or s["trace_id"] == trace_id]


def apply_clock_offset(
    spans: List[Dict[str, Any]], offset_s: float
) -> List[Dict[str, Any]]:
    """Land one process's span timestamps on the receiver's clock.  The
    head calls this at span ingest with its handshake-estimated per-conn
    offset; offset 0 returns the input unchanged (no copy)."""
    if not offset_s:
        return spans
    out = []
    for s in spans:
        c = dict(s)
        if isinstance(c.get("start"), (int, float)):
            c["start"] = c["start"] + offset_s
        if isinstance(c.get("end"), (int, float)):
            c["end"] = c["end"] + offset_s
        out.append(c)
    return out


def merge_process_spans(
    streams: List[tuple],
) -> List[Dict[str, Any]]:
    """Merge per-process span streams into ONE ordered timeline.

    `streams` is [(clock_offset_s, spans), ...] — each process's spans
    with the offset that lands its clock on the merger's.  Deterministic:
    the result is sorted by corrected start time with span_id as the
    tiebreak, so the same inputs always produce the same order (the
    clock-skew merge test asserts this).  This is the pure core of the
    head's merged `ray_tpu timeline`; the head applies offsets at ingest
    and the timeline export is already merged."""
    out: List[Dict[str, Any]] = []
    for offset_s, spans in streams:
        out.extend(apply_clock_offset(list(spans), offset_s))
    out.sort(key=lambda s: (s.get("start", 0.0), s.get("span_id") or ""))
    return out


def window_chrome_events(
    events: List[Dict[str, Any]],
    last: Optional[float] = None,
    since: Optional[float] = None,
    now: Optional[float] = None,
) -> List[Dict[str, Any]]:
    """Bound a chrome-trace event list to a time window (pure core of
    `ray_tpu timeline --last SECONDS / --since TS`).

    `last` = keep events whose END falls within the trailing window of
    that many seconds; `since` = keep events ending at/after that epoch
    timestamp (seconds).  `since` wins when both are given; neither
    returns the input unchanged.  Events carry `ts` (µs) and optionally
    `dur` (µs) — an event straddling the cutoff is KEPT (its tail is in
    the window; truncating would misrepresent a long-running span)."""
    if since is None and not last:
        return events
    now = time.time() if now is None else now
    cutoff_us = (since if since is not None else now - float(last)) * 1e6
    out = []
    for e in events:
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            out.append(e)  # malformed/clockless rows stay visible
            continue
        if ts + (e.get("dur") or 0) >= cutoff_us:
            out.append(e)
    return out


def spans_to_chrome_trace(spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Chrome-trace 'X' events for `ray_tpu timeline`-style viewing."""
    return [
        {
            "name": s["name"],
            "ph": "X",
            "ts": int(s["start"] * 1e6),
            "dur": int(max(s.get("end", s["start"]) - s["start"], 0) * 1e6),
            "pid": s.get("pid", 0),
            "tid": 0,
            "args": {
                "trace_id": s["trace_id"],
                "span_id": s["span_id"],
                "parent_span_id": s.get("parent_span_id"),
                **s.get("attrs", {}),
            },
        }
        for s in spans
    ]
