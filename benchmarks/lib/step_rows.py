"""The steady step from inside the library: what the five readers of the run
record's `steps` call (PERF.md section 3, "train step host side").

`ray_tpu/train/run_record.py` keeps one row for every period of the train
step, `train_step` entry to entry on the stepping thread (`StepClock`):
`step`, `start` (the worker's `time.time()`), `period_s`, the seconds the
library spent in that period sharding the batch (`make_batch_s`), dispatching
the step (`dispatch_s`) and inside `train.report` (`report_s`), and the
stepping thread's CPU seconds (`thread_cpu_s`); beside the rows,
`tokens_per_step`: the global batch's tokens as the program counted them.
In every run's record, traced or not.

The WINDOW's rows are those whose `start` is at or after the loop's
`t_window` (the same clock, the same process): the periods the loop's own
`step_ends` measure, seen from inside.  The period the profiler's start or
stop falls into is among them; every reader takes a median.

A record without `steps` (a parent commit's) reads as nothing in every
reader, and nothing here may take a run down (`run_record._never_raises`).
"""

from __future__ import annotations

import json
import statistics
from typing import Any, Dict, List, Optional

from benchmarks.lib import trace_scopes
from benchmarks.lib.run_record import _never_raises, record_of

KEY = "steps_window"
SLOTS = ("make_batch_s", "dispatch_s", "report_s")


def _summary(run, rows: List[Dict[str, Any]], tokens_per_step: Optional[int]) -> Dict[str, Any]:
    periods = sorted(r["period_s"] for r in rows)
    median = statistics.median(periods)
    out = {
        "count": len(rows), "tokens_per_step": tokens_per_step,
        "period_ms": 1e3 * median, "period_min_max_ms": [1e3 * periods[0], 1e3 * periods[-1]],
        **{k[:-2] + "_ms": 1e3 * statistics.median(r[k] for r in rows) for k in SLOTS},
        "library_ms": 1e3 * statistics.median(sum(r[k] for k in SLOTS) for r in rows),
    }
    # Totals, not a median of ratios: on the chip's host `time.thread_time` moves in ticks of 10 ms (PR 71:
    # rows read 0.0, 0.01, 0.02), so most rows of a 3 ms share read 0.  The stalled periods (over twice the
    # median, the clock's own rule: the profiler's start among them) are set apart.
    steady = [r for r in rows if r["period_s"] <= 2.0 * median]
    out["host_busy_pct"] = 100.0 * sum(r["thread_cpu_s"] for r in steady) / sum(r["period_s"] for r in steady)
    if tokens_per_step:
        out["tokens_per_s_per_chip"] = tokens_per_step / run["cell"]["chips"] / median
    return out


@_never_raises
def window(run) -> Optional[Dict[str, Any]]:
    """The summary of the window's rows, made once per run, left on `run`
    (so in the run's JSON) and printed as the line `[bench] steps {...}`.
    None where the record has no `steps` or the window no closed period."""
    if KEY not in run:
        run[KEY] = None
        record = record_of(run)
        steps = record.get("steps") if record else None
        if steps:
            rows = [r for r in steps["rows"] if r["start"] >= run["setup"]["t_window"]]
            if rows:
                run[KEY] = _summary(run, rows, steps.get("tokens_per_step"))
        print("[bench] steps " + json.dumps(run[KEY]), flush=True)
    return run[KEY]


def read(run, key: str) -> Optional[float]:
    got = window(run)
    return got.get(key) if got else None


@_never_raises
def mfu_pct(run) -> Optional[float]:
    """`mfu_pct` with the program's clock and count in the place of the
    loop's: the same needed FLOPs per token, the same peak."""
    rate = read(run, "tokens_per_s_per_chip")
    if rate is None:
        return None
    try:
        peak = trace_scopes.peak(run)
    except KeyError:  # a rehearsal's device: no peak on record, so no share of one
        return None
    needed = trace_scopes.builder(run).needed_flops_per_token(run["config"], run["traffic"]["seq_len"])
    return 100.0 * rate * needed / peak
