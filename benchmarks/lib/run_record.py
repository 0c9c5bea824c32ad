"""From a run's record to numbers: what the readers of the twelve metrics of
the run's wall clock call (PERF.md section 3, "a run's wall clock by name").

The record is the Train library's own (`ray_tpu/train/run_record.py`): one per
`fit()`, kept whether `RAY_TPU_TRACE` is set or not, and readable in this
process after `ray_tpu.shutdown()` through `ray_tpu.train.last_run_record()`.
It holds `spans` (one trace id, root `train::fit`: executor, worker spawn and
boot, the backend's jax import / chip wait / device open, the train function,
every `jax::trace` / `jax::lower` / `jax::compile`), `runtime_spans`
(`runtime::init`, `runtime::shutdown` and its stages), `stalls` (one event per
step whose period was over twice the median) and `reports` (delivery seconds).

`record_of` finds it once and leaves it on `run`, so it lands in the run's
JSON beside the rest.  A program that keeps no record (a parent commit) reads
as nothing in every reader.  Nothing here may take a run down: what a reader
calls goes through `_never_raises`, as in `trace_scopes.py`.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterable, List, Optional, Tuple

from benchmarks.lib.trace_reduce import clip, measure, union

KEY = "run_record"
# What `fit_unnamed_s` takes off the stretch from `fit()` to the loop's first line.
NAMED_BEFORE_LOOP = ("train::backend::import_jax", "train::backend::chip_wait", "train::backend::device_open")


def _never_raises(read):
    """The one boundary: whatever goes wrong under a reader is said on one
    line and reads as nothing.  A record never fails a run."""
    @functools.wraps(read)
    def guarded(*args, **kwargs):
        try:
            return read(*args, **kwargs)
        except Exception as e:  # noqa: BLE001
            print(f"[bench] run record FAILED: {type(e).__name__}: {e}"[:500], flush=True)
            return None

    return guarded


def record_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The record of the run's `fit()`, or None.  Looked up once per run."""
    if KEY not in run:
        try:
            from ray_tpu.train import last_run_record
        except ImportError:  # a program from before the record
            run[KEY] = None
        else:
            run[KEY] = last_run_record()
    return run[KEY]


def spans_named(record: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    return [s for s in record["spans"] + record.get("runtime_spans", []) if s["name"] == name]


def _intervals(spans: Iterable[Dict[str, Any]]) -> List[Tuple[float, float]]:
    return [(s["start"], s["end"]) for s in spans]


@_never_raises
def span_s(run, name: str) -> Optional[float]:
    """Seconds under the spans called `name`, the union of them."""
    record = record_of(run)
    found = spans_named(record, name) if record else []
    return measure(union(_intervals(found))) if found else None


def _fit_to_spawned(record) -> Optional[Tuple[float, float]]:
    fit, spawned = spans_named(record, "train::fit"), spans_named(record, "train::worker_group::spawn")
    if not fit or not spawned:
        return None
    return fit[0]["start"], max(s["end"] for s in spawned)


@_never_raises
def worker_spawn_s(run) -> Optional[float]:
    """`fit()`'s first line to the worker group answering its first call."""
    record = record_of(run)
    got = _fit_to_spawned(record) if record else None
    return got[1] - got[0] if got else None


@_never_raises
def fit_unnamed_s(run) -> Optional[float]:
    """`fit()`'s first line to the loop's first line, less what has a name
    there: the spawn (from `fit()`'s first line) and `NAMED_BEFORE_LOOP`."""
    record = record_of(run)
    got = _fit_to_spawned(record) if record else None
    if got is None:
        return None
    lo, hi = got[0], run["start"]["t_loop"]
    named = [got] + [i for n in NAMED_BEFORE_LOOP for i in _intervals(spans_named(record, n))]
    return (hi - lo) - measure(union(clip(named, lo, hi)))


def _in_setup(run, record, name: str) -> List[Dict[str, Any]]:
    """The spans called `name` that began between the loop's first line and
    the window's first step: the stretch `setup_s` measures."""
    lo, hi = run["start"]["t_loop"], run["setup"]["t_window"]
    return [s for s in spans_named(record, name) if lo <= s["start"] < hi]


@_never_raises
def setup_s_under(run, name: str) -> Optional[float]:
    """Seconds of set-up under `jax::trace`, `jax::lower` or `jax::compile`
    (their union: a trace the body of another runs is inside it)."""
    record = record_of(run)
    if not record or not spans_named(record, "train::worker::run_train_fn"):
        return None
    return measure(union(_intervals(_in_setup(run, record, name))))


@_never_raises
def setup_cache_misses(run) -> Optional[int]:
    record = record_of(run)
    if not record or not spans_named(record, "train::worker::run_train_fn"):
        return None
    return sum(1 for s in _in_setup(run, record, "jax::compile") if s["attrs"].get("cache") == "miss")


@_never_raises
def stalls_in_window(run) -> Optional[int]:
    """Stall events whose step began at or after the window's first step."""
    record = record_of(run)
    if not record:
        return None
    return sum(1 for e in record["stalls"] if e["start"] >= run["setup"]["t_window"])


@_never_raises
def report_delivery_ms(run) -> Optional[float]:
    record = record_of(run)
    median = record["reports"]["median_s"] if record else None
    return 1e3 * median if median is not None else None
