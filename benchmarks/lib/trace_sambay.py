"""A SambaY layer's share of the traced steps, by the names the program gives
it (`ray_tpu/models/transformer.py` `_s6_layer` / `_gmu_layer` / `_diff_layer`
/ `_diff_core`, `ray_tpu/ops/selective_scan.py`): inside `layer/attn_proj`
`s6/proj` (ln1, `W_in`, `W_x`, `W_dt` with the softplus, `W_out`, the residual
add), `s6/conv` (convolution + SiLU, the gate `y * silu(z)`), `gmu` (ln1,
`W_1`, the gate, `W_2`, the residual add) and `diff/proj` (ln1, `W_qkv` /
`W_q`, `W_o`, the residual add); inside `layer/attn_core` `s6/scan` (the whole
chunked selective scan), `diff/window` and `diff/full` (the head gathers and
the flash kernels of the windowed layers / of the full and cross layers) and
`diff/combine` (`a1 - lambda a2`, its RMSNorm and scale).

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/attn_proj` / `layer/attn_core` there.  This module reads the same
trace file with its own names THROUGH `trace_moe`'s reduction (the window, the
clipping, the self times: one implementation, lent another classifier as
`trace_kimi` lends it another name set).  Under `diff/window` and `diff/full`
an op whose path also holds a flash kernel's name counts under
`<name>/kernels`, so that the two attention rooflines divide by the kernels'
time alone, in every direction.  The step counter comes from the run's record.
Nothing here may take a run down (`trace_scopes._never_raises`), and a program
without these names (the parent of PR 40, every other cell) reads as nothing.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import re
from typing import Any, Dict, Optional

from benchmarks.lib import flops, run_record, trace_moe
from benchmarks.lib import trace_scopes as ts

SCOPES = ("s6/proj", "s6/conv", "s6/scan", "gmu", "diff/proj", "diff/window", "diff/full", "diff/combine")
KERNELS_UNDER = ("diff/window", "diff/full")
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
    process, printed as the line `[bench] sambay {...}` (seconds per step).
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
        print("[bench] sambay " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


@ts._never_raises
def share_pct(run, *names: str) -> Optional[float]:
    """Self time under `names`, every direction, as % of the traced window;
    nothing where the program has none of this module's names."""
    got = names_of(run)
    if not got or not any(got["seconds"].values()):
        return None
    return 100.0 * sum(got["seconds"][n] for n in names) / got["window_s"]


def _tokens_traced(run, steps: int) -> float:
    return run["summary"]["tokens_per_step"] / run["cell"]["chips"] * steps


def _builder(run):
    return importlib.import_module("benchmarks.builders." + run["config"]["kind"])


@ts._never_raises
def s6_scan_roofline_pct(run) -> Optional[float]:
    """The time the selective scan NEEDS in the traced steps on one chip over
    the device time under `s6/scan` in every direction (recompute is time, not
    work).  Needed: the longer of its bytes over the chip's HBM bandwidth
    (`builders/sambay_decoder.s6_scan_bytes_per_token`: x, z, dt, B, C read
    and y written once, and as much again twice for the backward) and its
    FLOPs over the bf16 peak (`s6_scan_flops_per_token`).  It is the BYTES
    bound at these sizes (4.6 ms against 0.2 ms a step): the scan is 18 flops
    a state element on arrays the fused form never writes."""
    got = names_of(run)
    seconds = got["seconds"]["s6/scan"] if got else 0.0
    if seconds <= 0:
        return None
    builder, config, peaks = _builder(run), run["config"], flops.load_peaks(run["device"]["kind"])
    tokens = _tokens_traced(run, got["steps"])
    needed_s = max(builder.s6_scan_bytes_per_token(config) * tokens / peaks["hbm_bytes_per_s"],
                   builder.s6_scan_flops_per_token(config) * tokens / peaks["bf16_flops_per_s"])
    return 100.0 * needed_s / seconds


def _attn_roofline_pct(run, name: str, needed_per_token: str) -> Optional[float]:
    got = names_of(run)
    seconds = got["seconds"][name + "/kernels"] if got else 0.0
    if seconds <= 0:
        return None
    needed = (getattr(_builder(run), needed_per_token)(run["config"], run["traffic"]["seq_len"])
              * _tokens_traced(run, got["steps"]))
    return 100.0 * needed / flops.load_peaks(run["device"]["kind"])["bf16_flops_per_s"] / seconds


@ts._never_raises
def swa_attn_roofline_pct(run) -> Optional[float]:
    """Needed attention FLOPs of the WINDOWED layers in the traced steps on one
    chip (`builders/sambay_decoder.swa_attention_flops_per_token`: both maps,
    the keys a query sees inside its window, forward + backward) over the
    chip's bf16 peak, over the flash kernels' device time under `diff/window`
    in every direction: the tiles' masked halves and the products the backward
    kernels compute again are time, not work."""
    return _attn_roofline_pct(run, "diff/window", "swa_attention_flops_per_token")


@ts._never_raises
def full_attn_roofline_pct(run) -> Optional[float]:
    """As `swa_attn_roofline_pct`, for the full-causal layer and the cross
    layers (`full_attention_flops_per_token`) under `diff/full`."""
    return _attn_roofline_pct(run, "diff/full", "full_attention_flops_per_token")


@run_record._never_raises
def window_tiles_visited_pct(run) -> Optional[float]:
    """`attn_window_tiles_visited_pct` of the program's step counters, as the
    run's record keeps it; nothing from a program that keeps no such counter."""
    counters = (run_record.record_of(run) or {}).get("step_counters") or {}
    return counters.get("attn_window_tiles_visited_pct")
