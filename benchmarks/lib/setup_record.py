"""From a run's record to where the seconds of a set-up are: what the readers of the twelve
metrics of PERF.md section 3, "set-up by program and the step's trace by part", call.

Set-up is the stretch `setup_s` measures, `run["start"]["t_loop"]` to `run["setup"]["t_window"]`,
as in `run_record.setup_s_under`.  Two splits of it, both from the record's `jax::trace` /
`jax::lower` / `jax::compile` spans (`ray_tpu/train/run_record.py`):

  * BY PROGRAM, from the spans' `fun_name` (every record has it): the step's, the apply's (the
    reference check's second whole trace of the model), the init's, every other program's (the
    plain reference's blocks, the one-op programs), and what no program of jax's was building.
    A point of the stretch is counted once, to the first program of `PROGRAM_ORDER` that covers
    it, so the five add up to the stretch;
  * INSIDE THE STEP'S TRACE, from `attrs["scopes"]` of the step's `jax::trace` span: the table
    `path -> [self seconds, entries]` the program's own `tracing.scope`s kept while jax traced,
    `attrs["kernels"]` (the names that are kernels' own) and `attrs["unscoped_s"]`.  A record from
    before the table (a parent commit's) reads as nothing here.

Nothing here may take a run down: what a reader calls goes through `run_record._never_raises`.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.lib.run_record import _in_setup, _intervals, _never_raises, record_of, spans_named
from benchmarks.lib.trace_reduce import clip, measure, union

# `ray_tpu.models.lm.PROGRAMS`, as data: a reader runs in the driver, which stays off jax, and
# on a parent commit that has no such name (`benchmarks/tests/test_setup_record.py` holds the two equal).
PROGRAMS = {"_init": "init", "_forward": "apply", "_train_step": "step"}
OTHER = "other"
PROGRAM_ORDER = ("step", "apply", "init", OTHER)
JAX_SPANS = ("jax::trace", "jax::lower", "jax::compile")
ENDS = frozenset(("embed", "final_norm", "lm_head", "loss", "optimizer"))
PRINTED = "setup_record_printed"


def program_of(fun_name: str) -> str:
    """`init`, `apply`, `step` or `other`, from a span's `fun_name` (`jit(...)` stripped)."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    return PROGRAMS.get(fun_name, OTHER)


def _by_program(run) -> Optional[Dict[str, float]]:
    """Seconds of the stretch by program, and `outside_jax`: under no span of jax's."""
    record = record_of(run)
    if not record or not spans_named(record, "train::worker::run_train_fn"):
        return None
    lo, hi = run["start"]["t_loop"], run["setup"]["t_window"]
    spans: Dict[str, List[Tuple[float, float]]] = {p: [] for p in PROGRAM_ORDER}
    for name in JAX_SPANS:
        for s in _in_setup(run, record, name):
            spans[program_of(s["attrs"].get("fun_name", ""))].extend(clip(_intervals([s]), lo, hi))
    out, covered, so_far = {}, [], 0.0
    for program in PROGRAM_ORDER:
        covered = union(covered + spans[program])
        out[program], so_far = measure(covered) - so_far, measure(covered)
    out["outside_jax"] = (hi - lo) - so_far
    return out


@_never_raises
def setup_program_s(run, program: str) -> Optional[float]:
    """Seconds of set-up under the trace, lowering and compile-or-load of `program` (one of
    `PROGRAM_ORDER`), or `outside_jax`: the stretch less all of them."""
    got = _by_program(run)
    return got[program] if got else None


def part_of(path: str, kernels) -> str:
    """Which part a path of the step's trace is counted to, the first of these it meets:
    `kernels` (the innermost name is a kernel's own), `ends` (a name of `ENDS` is in it),
    `autodiff` (the path is that one name), `stack` (every other)."""
    names = path.split("/")
    if names[-1] in kernels:
        return "kernels"
    if ENDS.intersection(names):
        return "ends"
    return "autodiff" if path == "autodiff" else "stack"


def _step_trace(run) -> Optional[Dict[str, Any]]:
    """The step's trace in set-up: `span_s`, the table summed over its `jax::trace` spans (as a
    rule one), `kernels`, `unscoped_s`.  None where the record has no table; where it has one,
    the first reader to ask also prints the run's one `[bench] trace-time` line."""
    record = record_of(run)
    if not record:
        return None
    spans = [s for s in _in_setup(run, record, "jax::trace")
             if program_of(s["attrs"].get("fun_name", "")) == "step" and "scopes" in s["attrs"]]
    if not spans:
        return None
    table: Dict[str, List[float]] = {}
    kernels = set()
    for s in spans:
        kernels.update(s["attrs"].get("kernels", ()))
        for path, (self_s, entries) in s["attrs"]["scopes"].items():
            row = table.setdefault(path, [0.0, 0])
            row[0] += self_s
            row[1] += entries
    trace = {"span_s": sum(s["end"] - s["start"] for s in spans), "scopes": table, "kernels": kernels,
             "unscoped_s": sum(s["attrs"]["unscoped_s"] for s in spans)}
    _say_once(run, trace)
    return trace


def _say_once(run, trace: Dict[str, Any]) -> None:
    """The one `[bench] trace-time` line of a run: the ten paths with the most self seconds."""
    if run.get(PRINTED):
        return
    run[PRINTED] = True
    top = sorted(trace["scopes"].items(), key=lambda kv: -kv[1][0])[:10]
    print("[bench] trace-time " + json.dumps({
        "step_trace_s": round(trace["span_s"], 3), "unscoped_s": round(trace["unscoped_s"], 3),
        "paths": len(trace["scopes"]), "entries": sum(row[1] for row in trace["scopes"].values()),
        "top": [[path, round(self_s, 3), entries] for path, (self_s, entries) in top]}), flush=True)


@_never_raises
def step_trace_s(run, part: Optional[str] = None) -> Optional[float]:
    """Seconds of the step's `jax::trace` span(s) in set-up: the whole (`part` None), the self
    seconds of the paths `part_of` counts to `part`, or `unscoped`: under no scope."""
    trace = _step_trace(run)
    if trace is None:
        return None
    if part is None:
        return trace["span_s"]
    if part == "unscoped":
        return trace["unscoped_s"]
    return sum(row[0] for path, row in trace["scopes"].items() if part_of(path, trace["kernels"]) == part)


@_never_raises
def step_trace_scope_entries(run) -> Optional[int]:
    """How many times Python entered a scope while the step was traced."""
    trace = _step_trace(run)
    return sum(row[1] for row in trace["scopes"].values()) if trace else None
