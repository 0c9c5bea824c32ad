"""A GLM-4.7-Flash step's share of the traced steps, by the names the program
gives it (`ray_tpu/models/mixers/mla.py`, `ray_tpu/models/moe.py`,
`ray_tpu/models/transformer.py` `mtp_rows`, `ray_tpu/models/lm.py`): inside
`layer/attn_proj` `mla/proj` (ln1, the low-rank q with its norm, the latent
with its norm, the rotation of the two rope parts, the key's concatenation,
`wo`, the residual add); inside `layer/mlp` `moe/shared` beside the four
`moe/*` names of `trace_moe`; and the OUTER name `mtp` around the whole
multi-token-prediction module: its two norms and `W_eh` (`mtp/proj`), its
block's own names, its pass through the head (`lm_head`) and its `loss`.

`trace_scopes.classify` takes the innermost name IT knows, so the module's
block stays `layer/attn_proj` / `layer/attn_core` / `layer/mlp` there and its
head `lm_head` / `loss`: the every-cell readers count the module with the
stack.  This module reads the same trace file with its own names THROUGH
`trace_moe`'s reduction (the window, the clipping, the self times: one
implementation, lent another classifier as `trace_mellum` lends it one): an
op counts under the innermost of `INNER` in its path, and under `mtp:<that>`
(`mtp:other` with none) when `mtp` is a component of the path too, in
whatever direction.  The step counters come from the run's record.  What is
this module's own is what the counts are divided into: the grouped matmuls'
FLOPs at the rows the TRACED steps gave the held experts
(`step_counter_series`), never a uniform router's expectation.  Nothing here
may take a run down (`trace_scopes._never_raises`), and a program without
these names or counters (the parent of PR 54, every other cell) reads as
nothing.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import re
from typing import Any, Dict, Optional

from benchmarks.lib import flops, trace_mellum, trace_moe
from benchmarks.lib import trace_scopes as ts

MODULE = "mtp"
ROUTED = trace_moe.NAMES  # router, dispatch, experts, combine: what the routed experts cost
INNER = ("mla/proj", "moe/shared", "lm_head", "loss") + ROUTED
OTHER = "other"
NAMES = INNER + tuple(f"{MODULE}:{name}" for name in INNER + (OTHER,))

_COMPONENT = re.compile(r"(?:(?<=/)|(?<=\()|^)(" + "|".join(map(re.escape, INNER + (MODULE,))) + r")(?=[/):]|$)")
_memo: Dict[str, Optional[Dict[str, Any]]] = {}


def classify(path: Optional[str]) -> Optional[str]:
    """The innermost of `INNER` in an op's `op_name` path, `mtp:`-prefixed
    where `mtp` is a component too (`mtp:other` with no inner name)."""
    found = _COMPONENT.findall(path) if path else None
    if not found:
        return None
    inner = [name for name in found if name != MODULE]
    name = inner[-1] if inner else None
    return f"{MODULE}:{name or OTHER}" if MODULE in found else name


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
    process, printed as the line `[bench] glm {...}` (seconds per step).
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
        print("[bench] glm " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


def _seconds(got, *names: str, module_only: bool = False) -> float:
    """Self time under `names`, in the module and (unless `module_only`) in the stack."""
    return sum(got["seconds"][f"{MODULE}:{n}"] + (0.0 if module_only else got["seconds"][n]) for n in names)


def _named(got) -> bool:
    """Whether the program has this cell's own names: a `mla/proj` or a `mtp` somewhere."""
    return bool(got) and (_seconds(got, "mla/proj") > 0 or _seconds(got, *INNER, OTHER, module_only=True) > 0)


@ts._never_raises
def share_pct(run, *names: str) -> Optional[float]:
    """Self time under `names` of `INNER`, stack and module together, every
    direction, as % of the traced window; nothing where the program has none
    of this cell's own names."""
    got = names_of(run)
    if not _named(got):
        return None
    return 100.0 * _seconds(got, *names) / got["window_s"]


@ts._never_raises
def module_share_pct(run, *names: str) -> Optional[float]:
    """Self time of the ops with `mtp` in their path (all of them, or those
    whose innermost name is one of `names`), every direction, as % of the
    traced window; nothing where the program names no module."""
    got = names_of(run)
    if not got or _seconds(got, *INNER, OTHER, module_only=True) <= 0:
        return None
    return 100.0 * _seconds(got, *(names or INNER + (OTHER,)), module_only=True) / got["window_s"]


def traced_held_rows(run) -> Optional[float]:
    """Rows the held experts of ALL expert layers, the module's block among
    them, multiplied in the traced steps together: `moe_held_rows_mean` (a
    step's mean over held experts and expert layers) of each traced step,
    from the record's series, times held experts times expert layers.  The
    loop's step i of the window is the context's `train_step` call `1 +
    warmup_steps + i` (the compile step and the warm-up come first).  None if
    the series misses a traced step."""
    record = trace_mellum._counters(run, "moe_held_rows_mean")
    trace = run.get("trace")
    if not record or not trace or "steps" not in trace:
        return None
    series = {step: values for step, values in record.get("step_counter_series") or ()}
    first = 1 + run["traffic"]["warmup_steps"]
    steps = range(first + trace["steps"][0], first + trace["steps"][1])
    if not all(s in series and "moe_held_rows_mean" in series[s] for s in steps):
        return None
    config = run["config"]
    builder = importlib.import_module("benchmarks.builders." + config["kind"])
    return sum(series[s]["moe_held_rows_mean"] for s in steps) * config["n_routed_experts"] * builder.expert_layers(config)


@ts._never_raises
def experts_roofline_pct(run) -> Optional[float]:
    """The grouped matmuls' needed FLOPs (three matrices, forward + backward)
    AT THE ROWS THE TRACED STEPS GAVE the held experts, over the chip's bf16
    peak, over the device time under `moe/experts` (stack and module) in every
    direction.  0.0 where the router gave the held experts nothing."""
    got = names_of(run)
    seconds = _seconds(got, "moe/experts") if got else 0.0
    rows = traced_held_rows(run)
    if seconds <= 0 or rows is None:
        return None
    print("[bench] held rows traced " + json.dumps({"rows": rows, "steps": got["steps"]}), flush=True)
    config = run["config"]
    builder = importlib.import_module("benchmarks.builders." + config["kind"])
    peak = flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * builder.expert_matmul_flops(config, rows) / peak / seconds


counter = trace_mellum.counter  # the newest value of a step counter; nothing from a program that keeps none
