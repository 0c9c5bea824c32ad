"""A Kimi Linear layer's share of the traced steps, by the names the program
gives it (`ray_tpu/models/transformer.py` `_kda_layer` / `_mla_layer`,
`ray_tpu/ops/kda.py`, `ray_tpu/models/moe.py`): inside `layer/attn_proj`
`kda/proj` (ln1, the fused q|k|v projection, both low-rank gates, beta, `wo`,
the residual add), `kda/conv` (convolutions + SiLU, L2 norms, the decay's
activation, the gated per-head RMSNorm) and `mla/proj`; inside
`layer/attn_core` `kda/scan` (the whole chunked recurrence) and the three
flash kernels; inside `layer/mlp` `moe/shared` beside the four `moe/*` names
of `trace_moe`.

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/attn_proj` / `layer/attn_core` / `layer/mlp` there.  This module
reads the same trace file with its own name set THROUGH `trace_moe`'s
reduction (the window, the clipping, the self times, the innermost-name rule:
one implementation, run here with more names), the flash kernels' seconds
from `trace_scopes.scopes_of`, and the step counters from the run's record.
Nothing here may take a run down (`trace_scopes._never_raises`), and a
program without these names (the parent of PR 37, every other cell) reads as
nothing.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import re
from typing import Any, Dict, Optional

from benchmarks.lib import flops, run_record, trace_moe
from benchmarks.lib import trace_scopes as ts

OWN_NAMES = ("kda/proj", "kda/conv", "kda/scan", "mla/proj", "moe/shared")
NAMES = OWN_NAMES + trace_moe.NAMES
ROUTED = trace_moe.NAMES  # router, dispatch, experts, combine: what the routed experts cost

_memo: Dict[str, Optional[Dict[str, Any]]] = {}


@contextlib.contextmanager
def _names_of_trace_moe(names):
    """`trace_moe.reduce_moe` sums self time per innermost name of ITS name
    set, which it reads from its module at call time: lend it another."""
    saved = trace_moe.NAMES, trace_moe._COMPONENT
    trace_moe.NAMES = names
    trace_moe._COMPONENT = re.compile(saved[1].pattern.replace(
        "|".join(map(re.escape, saved[0])), "|".join(map(re.escape, names))))
    try:
        yield
    finally:
        trace_moe.NAMES, trace_moe._COMPONENT = saved


def names_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Seconds of self time in the traced window per name of `NAMES`, once per
    process, printed as the line `[bench] kimi {...}` (seconds per step).
    None without a trace."""
    trace = run.get("trace")
    path = trace.get("path") if trace else None
    if not path:
        return None
    if path not in _memo:
        _memo[path] = None  # a failure is remembered as nothing to read
        loop = importlib.import_module("benchmarks.loops." + run["plan"]["loop"])
        with _names_of_trace_moe(NAMES):
            _memo[path] = got = trace_moe.reduce_moe(path, window_span=loop.STEP_SPAN)
        print("[bench] kimi " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


@ts._never_raises
def share_pct(run, *names: str) -> Optional[float]:
    """Self time under `names`, every direction, as % of the traced window;
    nothing where the program has none of this module's own names."""
    got = names_of(run)
    if not got or not any(got["seconds"][n] for n in OWN_NAMES):
        return None
    return 100.0 * sum(got["seconds"][n] for n in names) / got["window_s"]


def _tokens_traced(run, steps: int) -> float:
    return run["summary"]["tokens_per_step"] / run["cell"]["chips"] * steps


@ts._never_raises
def kda_scan_roofline_pct(run) -> Optional[float]:
    """Needed FLOPs of the KDA recurrence in the traced steps on one chip
    (`builders/kimi_linear_decoder.kda_scan_flops_per_token`: the chunked form
    at chunk 64, causal half, forward + backward) over the chip's bf16 peak,
    over the device time under `kda/scan` in every direction: what the
    backward recomputes is time, not work.  Against the COMPUTE peak, which a
    form that writes its [chunk, chunk] matrices, its decayed operands and its
    chunk states to HBM reads far below: the finding the metric exists for."""
    got = names_of(run)
    seconds = got["seconds"]["kda/scan"] if got else 0.0
    if seconds <= 0:
        return None
    config = run["config"]
    builder = importlib.import_module("benchmarks.builders." + config["kind"])
    needed = builder.kda_scan_flops_per_token(config) * _tokens_traced(run, got["steps"])
    return 100.0 * needed / flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"] / seconds


@ts._never_raises
def mla_attn_roofline_pct(run) -> Optional[float]:
    """Needed causal attention FLOPs of the MLA layers in the traced steps on
    one chip (`builders/kimi_linear_decoder.attention_flops_per_token`:
    `3 * S * H * (192 + 128)` a token and layer, forward + backward) over the
    chip's bf16 peak, over the three flash kernels' device time
    (`trace_scopes`): the recomputed forward call and the products the two
    backward kernels compute again are time, not work.  Nothing where the
    program has no `mla/proj` name (its kernels would then be another mixer's)."""
    got, scopes = names_of(run), ts.scopes_of(run)
    if not got or not scopes or got["seconds"]["mla/proj"] <= 0:
        return None
    seconds = sum(k["seconds"] for k in scopes["kernels"].values())
    if seconds <= 0:
        return None
    config = run["config"]
    builder = importlib.import_module("benchmarks.builders." + config["kind"])
    needed = (builder.attention_flops_per_token(config, run["traffic"]["seq_len"])
              * _tokens_traced(run, scopes["steps"]))
    return 100.0 * needed / flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"] / seconds


@run_record._never_raises
def held_rows_per_expert(run) -> Optional[float]:
    """Rows one held expert multiplied in a step, the mean over the held
    experts of every expert layer (`moe_held_rows_mean` of the step metrics,
    as the run's record keeps its newest value); nothing from a program that
    keeps no such counter.  The busiest expert's rows go to the line
    `[bench] held rows`."""
    record = run_record.record_of(run)
    counters = (record or {}).get("step_counters") or {}
    if "moe_held_rows_mean" not in counters:
        return None
    print("[bench] held rows " + json.dumps(counters), flush=True)
    return counters["moe_held_rows_mean"]
