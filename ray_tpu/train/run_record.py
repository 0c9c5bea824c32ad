"""The run record: what one `fit()` did with the seconds outside its steady
step, kept by the Train library in the tracing system (`util/tracing.py`).

Always on, whether `RAY_TPU_TRACE` is set or not:

  * LIFECYCLE spans under one trace id, root `train::fit`: the executor's
    start (placement group, worker spawn, the worker's boot, the backend's
    `on_start`: jax import, the wait for the chips, the chips' open), the
    train function, every trace / lowering / compile jax reports, shutdown.
    Of the traces the OUTERMOST are spans (`jax::trace`); one nested in
    another leaves no span, and leaves the seconds its body spent under the
    program's own scopes (`tracing.scope`: the model's `named_scope`s, which
    Python enters only while jax traces) in the outer span's `scopes`
    (`path -> [self seconds, entries]`), beside `kernels` (the names that are
    kernels' own) and `unscoped_s` (the span less the table).
    The driver's come from its own store, the workers' ride their `poll`
    replies (the same spans also take the flush to the head, for `ray_tpu
    timeline`);
  * the steady step: `StepClock`, entered by `LMTrainContext.train_step`,
    closes a period at every entry and keeps ONE row of it (`steps.rows`:
    the period, the seconds the library spent in it sharding the batch,
    dispatching the step and in `train.report`, the stepping thread's CPU
    seconds), with the tokens of a step and a summary (`steps.summary`:
    the step's period and tokens per second, from the program's own clock);
  * stalled steps: the same clock keeps the last periods and appends ONE
    event for a period over twice their median, with what tells a
    descheduled thread from a busy one (thread and process CPU seconds,
    involuntary switches, major faults, garbage collections);
  * report delivery: seconds from `session.report` in the worker to the
    driver's `on_report`, per report;
  * step counters: the newest values of the step metrics a training context
    names as counters (`LMTrainContext`: the `moe_held_rows_*` of a layer
    that holds a share of its experts, `moe_load_max_over_mean`), noted at
    every step without a fetch and read by the worker's `poll` once the
    device has them; and their values step by step (`step_counter_series`,
    the last `SERIES_KEPT` steps), so that a reader can take the rows the
    experts were given in the very steps a profile covers.

`JaxTrainer.fit` attaches the record to `Result.run_record`; the newest
stays readable through `last_run_record()` after `ray_tpu.shutdown()`, with
the runtime's own `runtime::init` and `runtime::shutdown` beside it.
"""

from __future__ import annotations

import bisect
import gc
import math
import resource
import statistics
import sys
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

from ray_tpu.util import tracing

# -- counters in the process's registry (`ray_tpu metrics` on a live job) --------

_counters: Dict[str, Any] = {}


def counters() -> Dict[str, Any]:
    """The record's three counters and two gauges, made on first use (a
    process that never trains registers none): `compiles` =
    `train_compiles_total{cache=hit|miss|off}`, `stalls` =
    `train_step_stalls_total`, `chip_wait` = `train_chip_wait_seconds`,
    `step_seconds` = `train_step_seconds`, `tokens_per_second` =
    `train_tokens_per_second`."""
    if not _counters:
        from ray_tpu.util.metrics import Counter, Gauge

        _counters.update(
            compiles=Counter(
                "train_compiles_total",
                "XLA programs compiled or loaded from the persistent cache",
                tag_keys=("cache",),
            ),
            stalls=Counter(
                "train_step_stalls_total",
                "train steps whose period was over twice the median of the last 4096",
            ),
            chip_wait=Counter(
                "train_chip_wait_seconds",
                "seconds train workers waited for another process to release the chips",
            ),
            step_seconds=Gauge(
                "train_step_seconds",
                "median period of the train step, entry to entry, over the last 4096 steps",
                tag_keys=("rank",),
            ),
            tokens_per_second=Gauge(
                "train_tokens_per_second",
                "tokens of the global batch over the median period of the train step",
                tag_keys=("rank",),
            ),
        )
    return _counters


# -- worker side: the steady step and its stalls ----------------------------------

SERIES_KEPT = 4096  # steps of the rows and of the counters' series a record keeps, the newest

_stalls: Deque[Dict[str, Any]] = deque(maxlen=256)
_rows: Deque[list] = deque(maxlen=SERIES_KEPT)  # closed periods not drained yet, oldest first; by position `ROW`
_stepping: Optional["StepClock"] = None  # the clock this process entered last: `train.report` marks its open period
_gc_totals = [0, 0.0, 0.0]  # collections, seconds, start of the one running
# What `StepClock.enter` stamps at a step's entry (a list, for its cost), by position:
# the last three are the slots `StepClock.mark` and `add_report_seconds` take.
_T, _WALL, _THREAD_CPU, _PROCESS_CPU, _SWITCHES, _FAULTS, _GC_N, _GC_S, MAKE_BATCH, DISPATCH, REPORT = range(11)
ROW = ("step", "start", "period_s", "make_batch_s", "dispatch_s", "report_s", "thread_cpu_s")
# The clocks, given as `tracing._clock` is: names a test moves by hand.
_clock = time.perf_counter  # periods and slots
_wall = time.time  # where a row or an event lies among the record's spans
_thread_cpu = time.thread_time


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        _gc_totals[2] = time.perf_counter()
    else:
        _gc_totals[0] += 1
        _gc_totals[1] += time.perf_counter() - _gc_totals[2]


class StepClock:
    """Periods of a train step, entry to entry, on the calling thread.

    `enter()` closes the period that is open and opens the next.  A closed
    period leaves one row (`ROW`) in the process's ring, which the worker's
    `poll` hands to the driver; one over `FACTOR` times the median of the
    clock's own ring (once it holds `MIN_PERIODS`) also appends one event to
    the process's stall events.  A few clock reads, one `getrusage` and one
    append per step, no span: about 3 us."""

    RING = 4096
    MIN_PERIODS = 5
    FACTOR = 2.0

    def __init__(self):
        self._ring: Deque[float] = deque()
        self._sorted: List[float] = []
        self._open: Optional[list] = None
        self._last_thread_cpu = 0.0
        self.steps = 0
        self.batch_shape: Optional[tuple] = None
        self.tokens_per_step: Optional[int] = None
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def enter(self) -> None:
        global _stepping
        now = _clock()
        ru = resource.getrusage(resource.RUSAGE_SELF)
        stamps = [now, _wall(), _thread_cpu(), ru.ru_utime + ru.ru_stime, ru.ru_nivcsw,
                  ru.ru_majflt, _gc_totals[0], _gc_totals[1], 0.0, 0.0, 0.0]  # in the order of _T .. REPORT
        prev, self._open = self._open, stamps
        _stepping = self
        if prev is None:
            return
        period = now - prev[_T]
        thread_cpu = stamps[_THREAD_CPU] - prev[_THREAD_CPU]
        _rows.append([self.steps, prev[_WALL], period, prev[MAKE_BATCH], prev[DISPATCH], prev[REPORT], thread_cpu])
        ring, ordered = self._ring, self._sorted
        if len(ring) >= self.MIN_PERIODS and period > self.FACTOR * ordered[len(ordered) // 2]:
            self._stalled(prev, stamps, period, thread_cpu, ordered[len(ordered) // 2])
        else:
            self._last_thread_cpu = thread_cpu
        if len(ring) == self.RING:
            del ordered[bisect.bisect_left(ordered, ring.popleft())]
        ring.append(period)
        bisect.insort(ordered, period)
        self.steps += 1

    def mark(self, slot: int, since: float) -> None:
        """Add the seconds since `since` to the open period's `MAKE_BATCH`
        or `DISPATCH` slot."""
        self._open[slot] += _clock() - since

    def note_batch(self, shape: tuple) -> None:
        """The global batch's `tokens` shape, once per shape: its product is
        the tokens of a step."""
        self.batch_shape, self.tokens_per_step = shape, math.prod(shape)

    def _stalled(self, prev: list, now: list, period: float, thread_cpu: float, median: float) -> None:
        excess = period - median
        # The share of the excess in which this thread was on no CPU: blocked
        # (on the device, a lock, I/O) or descheduled.  The steady step's own
        # CPU seconds are taken off first.
        on_cpu = min(max(thread_cpu - self._last_thread_cpu, 0.0), excess)
        _stalls.append({
            "step": self.steps, "start": prev[_WALL], "end": now[_WALL], "period_s": period, "median_s": median,
            "make_batch_s": prev[MAKE_BATCH], "dispatch_s": prev[DISPATCH], "thread_cpu_s": thread_cpu,
            "steady_thread_cpu_s": self._last_thread_cpu, "process_cpu_s": now[_PROCESS_CPU] - prev[_PROCESS_CPU],
            "involuntary_switches": now[_SWITCHES] - prev[_SWITCHES], "major_faults": now[_FAULTS] - prev[_FAULTS],
            "gc_collections": now[_GC_N] - prev[_GC_N], "gc_s": now[_GC_S] - prev[_GC_S],
            "off_cpu_pct": 100.0 * (1.0 - on_cpu / excess),
        })
        counters()["stalls"].inc()


def add_report_seconds(seconds: float) -> None:
    """`train.report`'s own seconds, to the `REPORT` slot of the period this
    process's stepping has open; nothing where none is (a loop that never
    calls `train_step`)."""
    clock = _stepping
    if clock is not None and clock._open is not None:
        clock._open[REPORT] += seconds


def drain_stalls() -> List[Dict[str, Any]]:
    out = []
    while _stalls:
        out.append(_stalls.popleft())
    return out


def drain_step_rows() -> List[list]:
    """The periods closed since the last `poll`, oldest first, each by `ROW`."""
    out = []
    while _rows:
        out.append(_rows.popleft())
    return out


def set_step_gauges(rank: int) -> Optional[int]:
    """At a `poll`, not per step: `train_step_seconds` (the median of the
    stepping clock's ring) and `train_tokens_per_second` for `ray_tpu
    metrics` on a live job, under the worker's `rank` (every rank of an SPMD
    job steps the same global batch: the cluster's view adds up what shares
    a tag).  Returns the clock's tokens per step, for the record."""
    clock = _stepping
    if clock is None:
        return None
    if clock._sorted:
        median, tags = clock._sorted[len(clock._sorted) // 2], {"rank": str(rank)}
        counters()["step_seconds"].set(median, tags=tags)
        if clock.tokens_per_step and median > 0:
            counters()["tokens_per_second"].set(clock.tokens_per_step / median, tags=tags)
    return clock.tokens_per_step


# -- worker side: step counters --------------------------------------------------

_noted: Dict[str, Any] = {}  # name -> the newest step's value, still on the device
_series: Deque[Any] = deque(maxlen=SERIES_KEPT)  # (step, {name: value}) not drained yet, oldest first


def note_step_counters(values: Dict[str, Any], step: Optional[int] = None) -> None:
    """Keep the newest step's counters (device scalars; no fetch here: the
    step that made them may still be running), and with `step`, the index
    of the step that made them, their place in the series."""
    _noted.update(values)
    if step is not None:
        _series.append((step, dict(values)))


def drain_step_series() -> List[List[Any]]:
    """[step, {name: float}] of the steps the device has finished, in order;
    the first unfinished step and those behind it wait for the next `poll`."""
    out = []
    while _series and all(map(_fetched, _series[0][1].values())):
        step, values = _series.popleft()
        out.append([step, {name: float(v) for name, v in values.items()}])
    return out


def _fetched(value: Any) -> bool:
    """Whether the device has finished a noted value (a plain number always has)."""
    return getattr(value, "is_ready", lambda: True)()


def drain_step_counters() -> Dict[str, float]:
    """The noted counters the device has finished, as floats; the others
    wait for the next `poll`."""
    out = {}
    for name, value in list(_noted.items()):
        if _fetched(value):
            out[name] = float(value)
            if _noted.get(name) is value:
                del _noted[name]
    return out


# -- worker side: what jax traces, lowers and compiles --------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",  # until a hit says otherwise
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# A trace shorter than this is not recorded: every `jnp` function called
# eagerly or under `eval_shape` is a trace of its own, hundreds of 0.1 ms.
_TRACE_FLOOR_S = 0.001
_thread = threading.local()
_fallback_parent: Optional[Dict[str, str]] = None
_listening = False


def set_fallback_parent(ctx: Optional[Dict[str, str]]) -> None:
    """The parent of a compile on a thread with no ambient span (the train
    function's own threads): `train::worker::run_train_fn`."""
    global _fallback_parent
    _fallback_parent = ctx


def _parent() -> Optional[Dict[str, str]]:
    return tracing.current_context() or _fallback_parent


def flush_traces() -> None:
    """Record this thread's outermost traces.  jax reports a trace when it
    ENDS, the traces nested in it (every jitted `jnp` function the body
    calls: a thousand for a toy model) before it: they are held back until
    an enclosing one swallows them or a lowering shows the trace is over.
    A swallowed trace leaves no span; the seconds its body spent under the
    program's scopes (`tracing.scope`) stay in the thread's table and land,
    with the outer body's own, on the span of the trace that encloses it."""
    held, _thread.traces = getattr(_thread, "traces", ()), []
    for start, end, fun_name in held:
        attrs: Dict[str, Any] = {"fun_name": fun_name}
        taken = tracing.take_scopes(start, end)
        if taken is not None:
            attrs["scopes"], attrs["kernels"] = taken
            attrs["unscoped_s"] = max((end - start) - sum(row[0] for row in taken[0].values()), 0.0)
        tracing.record_span("jax::trace", start, end, parent=_parent(), attrs=attrs, lifecycle=True)


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    if event == _TRACE:
        # The common case first, and cheap: thousands of sub-millisecond
        # traces per model, none of which can enclose a held one.
        if end - start < _TRACE_FLOOR_S:
            return
        held = getattr(_thread, "traces", None)
        if held is None:
            held = _thread.traces = []
        while held and held[-1][0] >= start:
            held.pop()
        held.append((start, end, str(kw.get("fun_name", ""))))
        return
    if event != _LOWER and event != _COMPILE:
        return
    try:
        flush_traces()
        attrs: Dict[str, Any] = {"fun_name": str(kw.get("fun_name", ""))}
        if event == _LOWER:
            tracing.record_span("jax::lower", start, end, parent=_parent(), attrs=attrs, lifecycle=True)
            return
        cache = getattr(_thread, "cache", None)
        if cache is not None:
            attrs["cache"] = cache
            if cache == "hit":
                attrs["retrieval_s"] = getattr(_thread, "retrieval_s", None)
        _thread.cache = _thread.retrieval_s = None
        counters()["compiles"].inc(tags={"cache": cache or "off"})
        tracing.record_span("jax::compile", start, end, parent=_parent(), attrs=attrs, lifecycle=True)
    except Exception:  # noqa: BLE001: runs inside jax's compile path, which it may not take down
        pass


def _on_event(event: str, **_kw) -> None:
    cache = _CACHE_EVENTS.get(event)
    if cache is not None:
        _thread.cache = cache


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _RETRIEVAL:
        _thread.retrieval_s = duration


def install_jax_listener() -> bool:
    """Register the one `jax.monitoring` listener of this process, once jax
    is imported (this module never imports it).  True if it is listening."""
    global _listening
    if not _listening and "jax" in sys.modules:
        import jax.monitoring as monitoring

        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return _listening


# -- driver side -----------------------------------------------------------------

_last: Optional["RunRecord"] = None


class RunRecord:
    """One `fit()`: its trace id, and what the workers' polls brought."""

    def __init__(self, ctx: Dict[str, str], runtime_trace_id: Optional[str] = None):
        self.trace_id = ctx["trace_id"]
        self.runtime_trace_id = runtime_trace_id
        self._worker_spans: Dict[str, Dict[str, Any]] = {}
        self.stalls: List[Dict[str, Any]] = []
        self.delivery_s: List[float] = []
        self.step_counters: Dict[str, float] = {}
        self.step_series: Deque[List[Any]] = deque(maxlen=SERIES_KEPT)
        self.step_rows: Deque[list] = deque(maxlen=SERIES_KEPT)  # `ROW` + the worker's rank
        self.tokens_per_step: Optional[int] = None
        self.polls = 0

    def add_poll(self, rank: int, reply: Dict[str, Any]) -> None:
        """Fold one worker's `poll` reply in; called on receipt, before the
        reports go to `on_report`."""
        now = time.time()
        self.polls += 1
        for s in reply.get("spans") or ():
            self._worker_spans[s["span_id"]] = s
        for e in reply.get("stalls") or ():
            self.stalls.append(dict(e, rank=rank))
        self.step_counters.update(reply.get("step_counters") or {})
        self.step_series.extend(reply.get("step_series") or ())
        self.step_rows.extend([*row, rank] for row in reply.get("step_rows") or ())
        self.tokens_per_step = reply.get("tokens_per_step") or self.tokens_per_step
        for rep in reply["reports"]:
            if "t" in rep:
                self.delivery_s.append(now - rep["t"])

    def to_dict(self) -> Dict[str, Any]:
        """The record as plain data (JSON takes it).  `spans` are those of
        the fit's trace, by start; `runtime_spans` this process's
        `runtime::init` and, once it ran, `runtime::shutdown` with its
        stages (another trace: a runtime outlives a fit)."""
        spans = {s["span_id"]: s for s in tracing.lifecycle_spans(self.trace_id)}
        spans.update(self._worker_spans)
        d = self.delivery_s
        return {
            "trace_id": self.trace_id,
            "spans": _by_start(spans.values()),
            "runtime_spans": _by_start(
                tracing.lifecycle_spans(self.runtime_trace_id) if self.runtime_trace_id else ()),
            "stalls": list(self.stalls),
            "step_counters": dict(self.step_counters),
            "step_counter_series": list(self.step_series),
            "steps": self._steps(),
            "reports": {"count": len(d), "polls": self.polls,
                        "median_s": statistics.median(d) if d else None,
                        "max_s": max(d) if d else None},
        }


    def _steps(self) -> Dict[str, Any]:
        """The steady step from inside: the newest `SERIES_KEPT` periods as
        rows (`ROW` and `rank`), the tokens of a step, and the rows' summary:
        the period's median, p10, p90 and max, the median of each slot and of
        the thread's CPU seconds, tokens per second over the median period, and
        `thread_cpu_share`: the thread's CPU seconds over the periods', both
        summed over the rows of at most `StepClock.FACTOR` medians (where
        `time.thread_time` moves in ticks of 10 ms, as on a TPU VM's host, the
        median row's CPU seconds read 0 and only the totals say anything)."""
        rows = [dict(zip(ROW + ("rank",), row)) for row in self.step_rows]
        summary: Dict[str, Any] = {"count": len(rows)}
        if rows:
            periods = sorted(r["period_s"] for r in rows)
            median = statistics.median(periods)
            summary.update(
                period_s={"median": median, "p10": periods[round(0.1 * (len(periods) - 1))],
                          "p90": periods[round(0.9 * (len(periods) - 1))], "max": periods[-1]},
                **{key: statistics.median(r[key] for r in rows) for key in ROW[3:]},
                tokens_per_s=self.tokens_per_step / median if self.tokens_per_step and median > 0 else None)
            steady = [r for r in rows if r["period_s"] <= StepClock.FACTOR * median]
            summary["thread_cpu_share"] = (sum(r["thread_cpu_s"] for r in steady)
                                           / max(sum(r["period_s"] for r in steady), 1e-12))
        return {"tokens_per_step": self.tokens_per_step, "rows": rows, "summary": summary}


def _by_start(spans) -> List[Dict[str, Any]]:
    return sorted(spans, key=lambda s: (s["start"], s["span_id"]))


def begin(ctx: Dict[str, str]) -> RunRecord:
    """The record of the `fit()` whose root span has context `ctx`; it is
    the newest from now on."""
    global _last
    rt_ctx = None
    try:
        from ray_tpu._private.runtime import get_runtime

        rt_ctx = get_runtime().trace_id
    except RuntimeError:  # an attached driver or a worker: the runtime is elsewhere
        pass
    _last = RunRecord(ctx, rt_ctx)
    return _last


def last_run_record() -> Optional[Dict[str, Any]]:
    """The record of this process's newest `fit()`, or None.  Readable after
    `ray_tpu.shutdown()`, and then it holds `runtime::shutdown` too."""
    return _last.to_dict() if _last is not None else None
