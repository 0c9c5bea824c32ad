"""Distributed tracing: OTel-compatible spans with context in task specs.

ray: python/ray/util/tracing/tracing_helper.py — the reference wraps
remote calls in OpenTelemetry spans and propagates the context INSIDE the
task spec (`_DictPropagator.inject_current_context`, :160), so a task's
execute span parents to its submitter's span across processes.  Same
design here:

  * opt-in (`RAY_TPU_TRACE=1` or `enable_tracing()`), zero overhead off;
  * the ACTIVE trace context lives in a contextvar; submission injects it
    into `spec.trace_ctx` as a W3C-traceparent-style dict, execution
    adopts it, so nested submits chain naturally;
  * spans always record to an in-process buffer that workers flush to the
    head (state API / timeline); when the `opentelemetry` API package is
    importable the same spans ALSO open real OTel spans — with no SDK
    installed those are no-ops, with a user-configured SDK they export
    wherever the user pointed it (the lazy-proxy pattern of the
    reference's _OpenTelemetryProxy:33).
"""

from __future__ import annotations

import contextvars
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

_enabled = os.environ.get("RAY_TPU_TRACE", "") not in ("", "0")
_current: "contextvars.ContextVar[Optional[Dict[str, str]]]" = contextvars.ContextVar(
    "raytpu_trace_ctx", default=None
)
_buffer: List[Dict[str, Any]] = []
_buffer_lock = threading.Lock()
_MAX_BUFFER = 10000

_otel_tracer = None
_otel_checked = False


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


def _otel():
    """Lazy OTel API tracer; None when the package is absent."""
    global _otel_tracer, _otel_checked
    if not _otel_checked:
        _otel_checked = True
        try:
            from opentelemetry import trace as _t

            _otel_tracer = _t.get_tracer("ray_tpu")
        except Exception:
            _otel_tracer = None
    return _otel_tracer


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


@contextmanager
def span(name: str, parent: Optional[Dict[str, str]] = None,
         attrs: Optional[Dict[str, Any]] = None):
    """Record one span.  `parent` (e.g. a spec's trace_ctx) wins over the
    ambient context; the new span becomes ambient for the duration, so
    anything submitted inside parents to it."""
    if not _enabled:
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
        "attrs": dict(attrs or {}),
        "pid": os.getpid(),
    }
    token = _current.set(ctx)
    otel = _otel()
    om = otel.start_as_current_span(name) if otel is not None else None
    if om is not None:
        om.__enter__()
    try:
        yield ctx
    finally:
        if om is not None:
            try:
                om.__exit__(None, None, None)
            except Exception:
                pass
        _current.reset(token)
        rec["end"] = time.time()
        with _buffer_lock:
            _buffer.append(rec)
            while len(_buffer) > _MAX_BUFFER:
                _buffer.pop(0)
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
) -> Optional[Dict[str, str]]:
    """Record an ALREADY-FINISHED span with explicit epoch timestamps.

    For intervals whose boundaries are only known after the fact — e.g.
    the "detect" stage of an elastic re-mesh starts on the head before the
    driver notices, and "resume" ends inside a report callback.  `ctx`
    pins the span's own ids so sibling spans recorded earlier can already
    have parented to it; returns the span's context for further chaining.
    """
    if not _enabled:
        return None
    c = {
        "trace_id": (ctx or parent or {}).get("trace_id") or _new_id(16),
        "span_id": (ctx or {}).get("span_id") or _new_id(8),
    }
    rec = {
        "name": name,
        "trace_id": c["trace_id"],
        "span_id": c["span_id"],
        "parent_span_id": (parent or {}).get("span_id"),
        "start": start,
        "end": end,
        "attrs": dict(attrs or {}),
        "pid": os.getpid(),
    }
    with _buffer_lock:
        _buffer.append(rec)
        while len(_buffer) > _MAX_BUFFER:
            _buffer.pop(0)
    return c


@contextmanager
def annotate(name: str):
    """A span of the PROGRAM's own host work on the profiler's clock.

    Opens a `jax.profiler.TraceAnnotation` if and only if jax is already
    imported (this module never imports it: the driver and the head stay
    off jax), so under a profiler session the span lands on `/host:CPU`
    next to the device planes; with tracing enabled the same interval is
    also recorded through `record_span`, so `ray_tpu timeline` shows it.
    With neither on it costs the annotation's one `TraceMe` check."""
    jax = sys.modules.get("jax")
    start = time.time() if _enabled else 0.0
    try:
        if jax is None:
            yield
        else:
            with jax.profiler.TraceAnnotation(name):
                yield
    finally:
        if _enabled:
            record_span(name, start, time.time(), parent=_current.get())


def drain_spans() -> List[Dict[str, Any]]:
    """Take the buffered spans (worker flush loops ship them to the head)."""
    with _buffer_lock:
        out, _buffer[:] = _buffer[:], []
    return out


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
