"""A Nemotron-H block's share of the traced steps, by the names the program
gives it (`ray_tpu/models/mixers/mamba2.py`, `ray_tpu/ops/ssm.py`,
`ray_tpu/models/moe.py`): `ssm/proj`, `ssm/conv` and `ssm/scan` of the Mamba-2
blocks (as `trace_ssm` has them), `moe/router`, `moe/dispatch`, `moe/experts`,
`moe/combine` and `moe/shared` of the expert blocks (as `trace_kimi`), and the
three flash kernels of the one attention block.

No reduction of its own: the names go THROUGH `trace_moe.reduce_moe` as
`trace_kimi` sends its own (the window, the clipping, the self times and the
innermost-name rule are `trace_reduce`'s and `trace_scopes`', one
implementation), the flash kernels' seconds come from `trace_scopes.scopes_of`,
the step counters from the run's record.  What is this module's own is what
the counts are divided into: the scan's needed FLOPs in the GROUPED chunked
form, and the grouped matmuls' FLOPs at the rows the TRACED steps gave the
held experts (`step_counter_series` of the run's record), never a uniform
router's expectation: a collapsed router gives a layer's held experts all of
the rows or none, by the seed.  Nothing here may take a run down
(`trace_scopes._never_raises`), and a program without these names or counters
reads as nothing.
"""

from __future__ import annotations

import importlib
import json
from typing import Any, Dict, Optional

from benchmarks.lib import flops, run_record, trace_kimi, trace_moe
from benchmarks.lib import trace_scopes as ts

SSM_NAMES = ("ssm/proj", "ssm/conv", "ssm/scan")
NAMES = SSM_NAMES + ("moe/shared",) + trace_moe.NAMES
ROUTED = ("moe/dispatch", "moe/experts", "moe/combine")  # what the routed experts cost behind the router

_memo: Dict[str, Optional[Dict[str, Any]]] = {}


def names_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Seconds of self time in the traced window per name of `NAMES`, once per
    process, printed as the line `[bench] nemotron_h {...}` (seconds per
    step).  None without a trace."""
    trace = run.get("trace")
    path = trace.get("path") if trace else None
    if not path:
        return None
    if path not in _memo:
        _memo[path] = None  # a failure is remembered as nothing to read
        loop = importlib.import_module("benchmarks.loops." + run["plan"]["loop"])
        with trace_kimi._names_of_trace_moe(NAMES):
            _memo[path] = got = trace_moe.reduce_moe(path, window_span=loop.STEP_SPAN)
        print("[bench] nemotron_h " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


def _builder(run):
    return importlib.import_module("benchmarks.builders." + run["config"]["kind"])


def _peak(run) -> float:
    return flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]


def _tokens_traced(run, steps: int) -> float:
    return run["summary"]["tokens_per_step"] / run["cell"]["chips"] * steps


@ts._never_raises
def share_pct(run, *names: str) -> Optional[float]:
    """Self time under `names`, every direction, as % of the traced window;
    nothing where the program has neither a Mamba-2 nor a shared-expert name."""
    got = names_of(run)
    if not got or not (got["seconds"]["ssm/scan"] and got["seconds"]["moe/shared"]):
        return None
    return 100.0 * sum(got["seconds"][n] for n in names) / got["window_s"]


@ts._never_raises
def scan_roofline_pct(run) -> Optional[float]:
    """Needed FLOPs of the selective scan in the traced steps
    (`builders/nemotron_h_decoder.ssd_flops_per_token`: the grouped chunked
    form at the published chunk, `C B^T` once a group, forward + backward)
    over the chip's bf16 peak, over the device time under `ssm/scan` in every
    direction: what the backward recomputes is time, not work."""
    got = names_of(run)
    seconds = got["seconds"]["ssm/scan"] if got else 0.0
    if seconds <= 0:
        return None
    needed = _builder(run).ssd_flops_per_token(run["config"]) * _tokens_traced(run, got["steps"])
    return 100.0 * needed / _peak(run) / seconds


@ts._never_raises
def attn_roofline_pct(run) -> Optional[float]:
    """Needed causal attention FLOPs of the attention block(s) in the traced
    steps (`6 * S * H * D` a token and block) over the chip's bf16 peak, over
    the three flash kernels' device time: the recomputed forward call and the
    products the two backward kernels compute again are time, not work."""
    scopes = ts.scopes_of(run)
    seconds = sum(k["seconds"] for k in scopes["kernels"].values()) if scopes else 0.0
    if seconds <= 0:
        return None
    needed = (_builder(run).attention_flops_per_token(run["config"], run["traffic"]["seq_len"])
              * _tokens_traced(run, scopes["steps"]))
    return 100.0 * needed / _peak(run) / seconds


def _counters(run, name: str) -> Optional[Dict[str, Any]]:
    record = run_record.record_of(run) or {}
    return record if name in (record.get("step_counters") or {}) else None


def traced_held_rows(run) -> Optional[float]:
    """Rows the held experts of ALL expert blocks multiplied in the traced
    steps together: `moe_held_rows_mean` (a step's mean over held experts and
    blocks) of each traced step, from the record's series, times held experts
    times expert blocks.  The loop's step i of the window is the context's
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
    per_mean = config["n_routed_experts"] * _builder(run).pattern(config).count("E")
    return sum(series[s]["moe_held_rows_mean"] for s in steps) * per_mean


@ts._never_raises
def experts_roofline_pct(run) -> Optional[float]:
    """The grouped matmuls' needed FLOPs (two matrices, forward + backward) AT
    THE ROWS THE TRACED STEPS GAVE the held experts, over the chip's bf16
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
