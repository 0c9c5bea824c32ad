"""A dots3-note-prev step's share of the traced steps, by the names the program
gives it (`ray_tpu/models/mixers/dsa.py`, `ray_tpu/models/moe.py`): inside
`layer/attn_proj` `mla/proj` (ln1, both latents with their norms and rescale,
the rope parts, the key's concatenation, the gate's projection) and
`attn/gate` (the head-wise gate, `wo` and the residual add); inside
`layer/attn_core` of a sliding layer `mla/window` (the flash kernels keep
their names inside it) and of a full layer `dsa/index` (the indexer's three
projections, its LayerNorm and rope, the scores), `dsa/topk` (the radix
select and the mask), `dsa/attn` (the core over the selected keys, both
directions: the kernels `dsa_attn_fwd`, `dsa_attn_bwd_dq`, `dsa_attn_bwd_dkv`
and the layout work around them) and `dsa/kl` (the target's kernel
`dsa_target` and the KL term); inside `layer/mlp` `moe/shared` beside the four
`moe/*` names of `trace_moe`.

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/attn_proj` / `layer/attn_core` / `layer/mlp` there.  This module
gives `trace_moe`'s reduction its own names and classifier (one
implementation).  The readers of `mla/proj`, the `moe/*` names and the step
counters, which other kinds have too, reach it through `trace_kind`.  What is
this module's own is the sparse core's roofline.  Nothing here may take a run
down (`trace_scopes._never_raises`), and a program without these names (the
parent of PR 66) reads as nothing.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.lib import flops, trace_moe
from benchmarks.lib import trace_scopes as ts

NAMES = ("mla/proj", "attn/gate", "mla/window", "dsa/index", "dsa/topk", "dsa/attn", "dsa/kl", "moe/shared") + trace_moe.NAMES

classify = trace_moe.innermost(NAMES)


def names_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Seconds of self time in the traced window per name of `NAMES`, once per
    process (`[bench] dots3 {...}`, seconds per step).  None without a trace."""
    return trace_moe.reduced(run, "dots3", NAMES, classify)


@ts._never_raises
def attn_roofline_pct(run) -> Optional[float]:
    """The time the sparse core NEEDS in the traced steps on one chip over the
    device time under `dsa/attn` in every direction.  Needed: the longer of its
    FLOPs over the bf16 peak (`builders/sparse_mla_moe_decoder.
    selected_flops_per_layer`: QK^T and PV over the SELECTED pairs, `min(t + 1,
    index_topk)` a query, forward + backward) and its bytes over the chip's HBM
    bandwidth (`selected_bytes_per_layer`), summed over the full layers.  The
    unselected pairs of a masked tile, the products the backward kernels
    compute again and the transposes around the kernels are time, not work."""
    got = names_of(run)
    seconds = got["seconds"]["dsa/attn"] if got else 0.0
    if seconds <= 0:
        return None
    kind, config, seq = ts.builder(run), run["config"], run["traffic"]["seq_len"]
    peaks = flops.load_peaks(run["device"]["kind"])
    tokens = ts.tokens_traced(run, got["steps"]) * kind.layer_kinds(config).count(kind.FULL)
    needed_s = max(kind.selected_flops_per_layer(config, seq) * tokens / peaks["bf16_flops_per_s"],
                   kind.selected_bytes_per_layer(config, seq) * tokens / peaks["hbm_bytes_per_s"])
    return 100.0 * needed_s / seconds
