"""A Mellum 2 layer's share of the traced steps, by the names the program
gives it (`ray_tpu/models/mixers/attention.py`, `ray_tpu/models/moe.py`):
inside `layer/attn_core` ONE of `attn/window` and `attn/full`, which a model
with `layer_windows` puts around the core of a window layer and of a full
layer (the three flash kernels keep their names inside both); inside
`layer/mlp` the four `moe/*` names of `trace_moe`.

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/attn_core` / `layer/mlp` there.  This module reads the same
trace file with its own names THROUGH `trace_moe`'s reduction (the window, the
clipping, the self times: one implementation, lent another classifier as
`trace_sambay` lends it one).  Under the two `attn/*` names an op whose path
also holds a flash kernel's name counts under `<name>/kernels`, so that the
two attention rooflines divide by the kernels' time alone, in every
direction.  The step counters come from the run's record.  What is this
module's own is what the counts are divided into: causal attention at the
keys each kind of layer really sees, and the grouped matmuls' FLOPs at the
rows the TRACED steps gave the held experts (`step_counter_series`), never a
uniform router's expectation.  Nothing here may take a run down
(`trace_scopes._never_raises`), and a program without these names or counters
(the parent of PR 50, every other cell) reads as nothing.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import re
from typing import Any, Dict, Optional

from benchmarks.lib import flops, run_record, trace_moe
from benchmarks.lib import trace_scopes as ts

KERNELS_UNDER = ("attn/window", "attn/full")
ROUTED = trace_moe.NAMES  # router, dispatch, experts, combine: what the routed experts cost (there is no shared one)
SCOPES = KERNELS_UNDER + ROUTED
NAMES = SCOPES + tuple(name + "/kernels" for name in KERNELS_UNDER)

_COMPONENT = re.compile(r"(?:(?<=/)|(?<=\()|^)(" + "|".join(map(re.escape, SCOPES)) + r")(?=[/):]|$)")
_KERNEL = re.compile("|".join(map(re.escape, ts.KERNELS)))
_memo: Dict[str, Optional[Dict[str, Any]]] = {}


def classify(path: Optional[str]) -> Optional[str]:
    """The innermost of `SCOPES` in an op's `op_name` path, in whatever
    direction; a flash kernel under `KERNELS_UNDER` as `<name>/kernels`."""
    found = _COMPONENT.findall(path) if path else None
    if not found:
        return None
    name = found[-1]
    return name + "/kernels" if name in KERNELS_UNDER and _KERNEL.search(path) else name


@contextlib.contextmanager
def _lent_to_trace_moe():
    """`trace_moe.reduce_moe` sums self time per name its `classify` gives,
    both read from its module at call time: lend it this module's."""
    saved = trace_moe.NAMES, trace_moe.classify
    trace_moe.NAMES, trace_moe.classify = NAMES, classify
    try:
        yield
    finally:
        trace_moe.NAMES, trace_moe.classify = saved


def names_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Seconds of self time in the traced window per name of `NAMES`, once per
    process, printed as the line `[bench] mellum {...}` (seconds per step).
    None without a trace."""
    trace = run.get("trace")
    path = trace.get("path") if trace else None
    if not path:
        return None
    if path not in _memo:
        _memo[path] = None  # a failure is remembered as nothing to read
        loop = importlib.import_module("benchmarks.loops." + run["plan"]["loop"])
        with _lent_to_trace_moe():
            _memo[path] = got = trace_moe.reduce_moe(path, window_span=loop.STEP_SPAN)
        print("[bench] mellum " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


def _under(got, name: str) -> float:
    """Self time under one of `KERNELS_UNDER`, its kernels and what is around them."""
    return got["seconds"][name] + got["seconds"][name + "/kernels"]


@ts._never_raises
def attn_share_pct(run, name: str) -> Optional[float]:
    """Self time under `attn/window` or `attn/full`, every direction, as % of
    the traced window; nothing where the program names neither."""
    got = names_of(run)
    if not got or not any(_under(got, n) for n in KERNELS_UNDER):
        return None
    return 100.0 * _under(got, name) / got["window_s"]


@ts._never_raises
def routed_share_pct(run, names=ROUTED) -> Optional[float]:
    """Self time under `names` of the four `moe/*` names (all four: what the
    routed experts cost), every direction, as % of the traced window; nothing
    where the program names none of the four."""
    got = names_of(run)
    if not got or not any(got["seconds"][n] for n in ROUTED):
        return None
    return 100.0 * sum(got["seconds"][n] for n in names) / got["window_s"]


def _builder(run):
    return importlib.import_module("benchmarks.builders." + run["config"]["kind"])


def _peak(run) -> float:
    return flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]


def _tokens_traced(run, steps: int) -> float:
    return run["summary"]["tokens_per_step"] / run["cell"]["chips"] * steps


@ts._never_raises
def attn_roofline_pct(run, name: str, needed_per_token: str) -> Optional[float]:
    """Needed causal attention FLOPs of ONE kind of layer in the traced steps
    (`builders/swa_moe_decoder.<needed_per_token>`: forward + backward at the
    keys a query of that kind really sees) over the chip's bf16 peak, over the
    flash kernels' device time under `name` in every direction: the masked
    parts of the tiles a window layer visits, the recomputed forward call and
    the products the backward kernels compute again are time, not work."""
    got = names_of(run)
    seconds = got["seconds"][name + "/kernels"] if got else 0.0
    if seconds <= 0:
        return None
    needed = (getattr(_builder(run), needed_per_token)(run["config"], run["traffic"]["seq_len"])
              * _tokens_traced(run, got["steps"]))
    return 100.0 * needed / _peak(run) / seconds


def _counters(run, name: str) -> Optional[Dict[str, Any]]:
    record = run_record.record_of(run) or {}
    return record if name in (record.get("step_counters") or {}) else None


def traced_held_rows(run) -> Optional[float]:
    """Rows the held experts of ALL layers multiplied in the traced steps
    together: `moe_held_rows_mean` (a step's mean over held experts and
    layers) of each traced step, from the record's series, times held experts
    times layers.  The loop's step i of the window is the context's
    `train_step` call `1 + warmup_steps + i` (the compile step and the
    warm-up come first).  None if the series misses a traced step."""
    record = _counters(run, "moe_held_rows_mean")
    trace = run.get("trace")
    if not record or not trace or "steps" not in trace:
        return None
    series = {step: values for step, values in record.get("step_counter_series") or ()}
    first = 1 + run["traffic"]["warmup_steps"]
    steps = range(first + trace["steps"][0], first + trace["steps"][1])
    if not all(s in series and "moe_held_rows_mean" in series[s] for s in steps):
        return None
    config = run["config"]
    return sum(series[s]["moe_held_rows_mean"] for s in steps) * config["num_experts"] * config["num_hidden_layers"]


@ts._never_raises
def experts_roofline_pct(run) -> Optional[float]:
    """The grouped matmuls' needed FLOPs (three matrices, forward + backward)
    AT THE ROWS THE TRACED STEPS GAVE the held experts, over the chip's bf16
    peak, over the device time under `moe/experts` in every direction.  0.0
    where the router gave the held experts nothing."""
    got = names_of(run)
    seconds = got["seconds"]["moe/experts"] if got else 0.0
    rows = traced_held_rows(run)
    if seconds <= 0 or rows is None:
        return None
    print("[bench] held rows traced " + json.dumps({"rows": rows, "steps": got["steps"]}), flush=True)
    return 100.0 * _builder(run).expert_matmul_flops(run["config"], rows) / _peak(run) / seconds


@run_record._never_raises
def counter(run, name: str) -> Optional[float]:
    """The newest value of the step counter `name`; nothing from a program that keeps none."""
    record = _counters(run, name)
    if record is None:
        return None
    print("[bench] step counters " + json.dumps(record["step_counters"]), flush=True)
    return record["step_counters"][name]
