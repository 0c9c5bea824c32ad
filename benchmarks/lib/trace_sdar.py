"""An SDAR step's share of the traced steps, by the names the program gives it
(`ray_tpu/models/mixers/attention.py`, `ray_tpu/models/lm.py`,
`ray_tpu/models/moe.py`): inside `layer/attn_core` the scope
`attn/block_diffusion`, which a block-diffusion model puts around the core
under its mask (the three flash kernels keep their names inside it: one call
a layer and direction over the 2S rows `[x_t ‖ x_0]`); `diffusion/noise`, the
step's draw of rates and masks and the noisy copy, on the device; inside
`layer/mlp` the four `moe/*` names of `trace_moe`.

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/attn_core` / `layer/mlp` (and the noise, which lies under none of
its names, `unscoped`) there.  This module reads the same trace file with its
own names THROUGH `trace_moe`'s reduction (the window, the clipping, the self
times: one implementation, lent another classifier as `trace_mellum` lends it
one).  Under `attn/block_diffusion` an op whose path also holds a flash
kernel's name counts under `attn/block_diffusion/kernels`, so that the
roofline divides by the kernels' time alone, in every direction.  The step
counters come from the run's record (`trace_mellum`'s readers: this
configuration's file counts held experts and layers under the same keys).
The line `[bench] sdar` carries every name's seconds a step (the step by scope
of PERF.md section 5); of the eleven readers ISSUE 62 lists, three are in
`benchmarks/layer_metrics/`, one a layer that runs and can move
(`sdar_attn_roofline`, `sdar_attn_mask_fill_pct`, `sdar_experts_roofline`):
BENCHMARK.json may hold 128 per-layer metrics (the round's contract for the
file: "`per_layer`: 1 to 128 metrics", refused before a run otherwise) and
held 125.  What is this module's own is what the counts are divided into:
attention at the mask's true pairs (`builders/block_diffusion_moe_decoder.
attention_flops_per_token`), a DATA token the unit, and the grouped matmuls at
the rows the traced steps gave the held experts (`expert_matmul_flops`).  Nothing here may take a
run down (`trace_scopes._never_raises`), and a program without these names or
counters (the parent of PR 62, every other cell) reads as nothing.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import re
from typing import Any, Dict, Optional

from benchmarks.lib import trace_mellum, trace_moe
from benchmarks.lib import trace_scopes as ts

CORE, NOISE = "attn/block_diffusion", "diffusion/noise"
KERNELS = CORE + "/kernels"
ROUTED = trace_moe.NAMES  # router, dispatch, experts, combine: what the routed experts cost (there is no shared one)
SCOPES = (CORE, NOISE) + ROUTED  # what the program names
NAMES = SCOPES + (KERNELS,)

_COMPONENT = re.compile(r"(?:(?<=/)|(?<=\()|^)(" + "|".join(map(re.escape, SCOPES)) + r")(?=[/):]|$)")
_KERNEL = re.compile("|".join(map(re.escape, ts.KERNELS)))
_memo: Dict[str, Optional[Dict[str, Any]]] = {}


def classify(path: Optional[str]) -> Optional[str]:
    """The innermost of `SCOPES` in an op's `op_name` path, in whatever
    direction; a flash kernel under `CORE` as `KERNELS`."""
    found = _COMPONENT.findall(path) if path else None
    if not found:
        return None
    return KERNELS if found[-1] == CORE and _KERNEL.search(path) else found[-1]


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
    process, printed as the line `[bench] sdar {...}` (seconds per step).
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
        print("[bench] sdar " + json.dumps(
            {"steps": got["steps"], "s_per_step": {k: v / got["steps"] for k, v in got["seconds"].items()}}
            if got else None), flush=True)
    return _memo[path]


@ts._never_raises
def attn_roofline_pct(run) -> Optional[float]:
    """Needed attention FLOPs under the block-diffusion mask in the traced
    steps (the builder's `attention_flops_per_token`: `12 * L * (S + B) * H *
    D` a data token, the mask's true pairs forward + backward) over the
    chip's bf16 peak, over the flash kernels' device time under
    `attn/block_diffusion` in every direction: the masked part of a visited
    tile, the recomputed forward call and the products the two backward
    kernels compute again are time, not work."""
    got = names_of(run)
    seconds = got["seconds"][KERNELS] if got else 0.0
    if seconds <= 0:
        return None
    needed = (trace_mellum._builder(run).attention_flops_per_token(run["config"], run["traffic"]["seq_len"])
              * trace_mellum._tokens_traced(run, got["steps"]))
    return 100.0 * needed / trace_mellum._peak(run) / seconds


@ts._never_raises
def experts_roofline_pct(run) -> Optional[float]:
    """The grouped matmuls' needed FLOPs (three matrices, forward + backward)
    AT THE ROWS THE TRACED STEPS GAVE the held experts (`trace_mellum.
    traced_held_rows`: all 2S rows of a step are routed), over the chip's
    bf16 peak, over the device time under `moe/experts` in every direction.
    0.0 where the router gave the held experts nothing."""
    got = names_of(run)
    seconds = got["seconds"]["moe/experts"] if got else 0.0
    rows = trace_mellum.traced_held_rows(run)
    if seconds <= 0 or rows is None:
        return None
    print("[bench] held rows traced " + json.dumps({"rows": rows, "steps": got["steps"]}), flush=True)
    return 100.0 * trace_mellum._builder(run).expert_matmul_flops(run["config"], rows) / trace_mellum._peak(run) / seconds


counter = trace_mellum.counter  # the newest value of a step counter; nothing from a program that keeps none
