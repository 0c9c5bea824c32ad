"""A Qwen3-Next step's share of the traced steps, by the names the program
gives it (`ray_tpu/models/mixers/gdn.py`, `ray_tpu/models/mixers/attention.py`,
`ray_tpu/ops/kda.py`, `ray_tpu/models/moe.py`): inside `layer/attn_proj` of a
delta layer `gdn/proj` (ln1, the fused q|k|v|z projection, beta's and the
decay's logits, `wo`, the residual add) and `gdn/conv` (convolutions + SiLU,
L2 norms, the decay's activation, the gated per-head RMSNorm); inside its
`layer/attn_core` `gdn/scan` (the whole chunked recurrence); in an attention
layer `attn/gate` (the sigmoid gate on the core's output) and, under NO name
of its own, everything else of its mixer: the projections, the per-head
norms, the rope, the three flash kernels (`attn/mixer` here: an op under
`layer/attn_proj` or `layer/attn_core` that none of this module's names
reaches); inside `layer/mlp` `moe/shared` beside the four `moe/*` names of
`trace_moe`.

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/attn_proj` / `layer/attn_core` / `layer/mlp` there.  This module
reads the same trace file with its own names THROUGH `trace_moe`'s reduction
(the window, the clipping, the self times: one implementation, lent another
classifier as `trace_mellum` lends it one), the flash kernels' seconds from
`trace_scopes.scopes_of`, and the step counters from the run's record
(`trace_mellum`'s readers: this configuration's file counts held experts and
layers under the same keys).  What is this module's own is what the counts
are divided into.  Nothing here may take a run down
(`trace_scopes._never_raises`), and a program without these names or counters
(the parent of PR 57, every other cell) reads as nothing.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import re
from typing import Any, Dict, Optional

from benchmarks.lib import trace_mellum, trace_moe
from benchmarks.lib import trace_scopes as ts

GDN = ("gdn/proj", "gdn/conv", "gdn/scan")
GATE, MIXER = "attn/gate", "attn/mixer"
ROUTED = trace_moe.NAMES  # router, dispatch, experts, combine: what the routed experts cost
SCOPES = GDN + (GATE, "moe/shared") + ROUTED  # what the program names
NAMES = SCOPES + (MIXER,)

_COMPONENT = re.compile(r"(?:(?<=/)|(?<=\()|^)(" + "|".join(map(re.escape, SCOPES)) + r")(?=[/):]|$)")
_OF_A_MIXER = re.compile(r"(?:(?<=/)|(?<=\()|^)(layer/attn_proj|layer/attn_core)(?=[/):]|$)")
_memo: Dict[str, Optional[Dict[str, Any]]] = {}


def classify(path: Optional[str]) -> Optional[str]:
    """The innermost of `SCOPES` in an op's `op_name` path, in whatever
    direction; `attn/mixer` for an op of a mixer that none of them reaches."""
    if not path:
        return None
    found = _COMPONENT.findall(path)
    if found:
        return found[-1]
    return MIXER if _OF_A_MIXER.search(path) else None


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
    process, printed as the line `[bench] qwen3_next {...}` (seconds per
    step).  None without a trace."""
    trace = run.get("trace")
    path = trace.get("path") if trace else None
    if not path:
        return None
    if path not in _memo:
        _memo[path] = None  # a failure is remembered as nothing to read
        loop = importlib.import_module("benchmarks.loops." + run["plan"]["loop"])
        with _lent_to_trace_moe():
            _memo[path] = got = trace_moe.reduce_moe(path, window_span=loop.STEP_SPAN)
        print("[bench] qwen3_next " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


def _named(got) -> bool:
    """Whether the program has this cell's own names: a `gdn/*` somewhere."""
    return bool(got) and any(got["seconds"][n] > 0 for n in GDN)


@ts._never_raises
def share_pct(run, *names: str) -> Optional[float]:
    """Self time under `names`, every direction, as % of the traced window;
    nothing where the program has none of this cell's own names."""
    got = names_of(run)
    if not _named(got):
        return None
    return 100.0 * sum(got["seconds"][n] for n in names) / got["window_s"]


@ts._never_raises
def gdn_scan_roofline_pct(run) -> Optional[float]:
    """Needed FLOPs of the delta rule in the traced steps
    (`builders/qwen3_next_decoder.gdn_scan_flops_per_token`: the rule with ONE
    decay a head in its chunked form at chunk 64, causal half, forward +
    backward) over the chip's bf16 peak, over the device time under `gdn/scan`
    in every direction: the backward's recompute, and whatever a kernel
    written for a decay per channel does beyond the scalar rule, is time, not
    work."""
    got = names_of(run)
    seconds = got["seconds"]["gdn/scan"] if got else 0.0
    if seconds <= 0:
        return None
    needed = (trace_mellum._builder(run).gdn_scan_flops_per_token(run["config"])
              * trace_mellum._tokens_traced(run, got["steps"]))
    return 100.0 * needed / trace_mellum._peak(run) / seconds


@ts._never_raises
def gated_attn_roofline_pct(run) -> Optional[float]:
    """Needed causal attention FLOPs of the attention layers in the traced
    steps (`builders/qwen3_next_decoder.attention_flops_per_token`: `6 * S *
    16 * 256` a token and layer, forward + backward) over the chip's bf16
    peak, over the three flash kernels' device time (`trace_scopes`): the
    recomputed forward call and the products the two backward kernels compute
    again are time, not work.  Nothing where the program has no `gdn/*` name
    (its kernels would then be another model's)."""
    got, scopes = names_of(run), ts.scopes_of(run)
    if not _named(got) or not scopes:
        return None
    seconds = sum(k["seconds"] for k in scopes["kernels"].values())
    if seconds <= 0:
        return None
    needed = (trace_mellum._builder(run).attention_flops_per_token(run["config"], run["traffic"]["seq_len"])
              * trace_mellum._tokens_traced(run, scopes["steps"]))
    return 100.0 * needed / trace_mellum._peak(run) / seconds


@ts._never_raises
def experts_roofline_pct(run) -> Optional[float]:
    """The grouped matmuls' needed FLOPs (three matrices, forward + backward)
    AT THE ROWS THE TRACED STEPS GAVE the held experts
    (`trace_mellum.traced_held_rows`: the record's series, held experts x
    layers), over the chip's bf16 peak, over the device time under
    `moe/experts` in every direction.  0.0 where the router gave the held
    experts nothing."""
    got = names_of(run)
    seconds = got["seconds"]["moe/experts"] if _named(got) else 0.0
    rows = trace_mellum.traced_held_rows(run)
    if seconds <= 0 or rows is None:
        return None
    print("[bench] held rows traced " + json.dumps({"rows": rows, "steps": got["steps"]}), flush=True)
    return 100.0 * trace_mellum._builder(run).expert_matmul_flops(run["config"], rows) / trace_mellum._peak(run) / seconds


counter = trace_mellum.counter  # the newest value of a step counter; nothing from a program that keeps none
