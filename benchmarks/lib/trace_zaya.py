"""A ZAYA1 step's share of the traced steps, by the names the program gives
it (`ray_tpu/models/mixers/cca.py`, `ray_tpu/models/moe.py`): inside
`layer/attn_proj` `cca/proj` (ln1, the three projections into the latents with
the value's shift, and at the layer's end `wo` and the join through the
learned residual scaling) and `cca/mix` (both convolutions over the q|k latent,
the q-k mean, the unit norm, tau, the rope); the core stays `layer/attn_core`
with the flash kernels' names; inside `layer/mlp` the four `moe/*` names of
`trace_moe`, `moe/router` the whole router (down-projection, depth average,
RMSNorm, the three maps, softmax, argmax).

`trace_scopes.classify` takes the innermost name IT knows, so all of this
stays `layer/attn_proj` / `layer/attn_core` / `layer/mlp` there.  This module
gives `trace_moe`'s reduction its own names and classifier (one
implementation); the readers of the `moe/*` names and the step counters, which
other kinds have too, reach it through `trace_kind`.  What is this module's own
is the mixing's roofline.  Nothing here may take a run down
(`trace_scopes._never_raises`), and a program without these names (the parent
of PR 68) reads as nothing.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.lib import flops, trace_moe
from benchmarks.lib import trace_scopes as ts

NAMES = ("cca/proj", "cca/mix") + trace_moe.NAMES

classify = trace_moe.innermost(NAMES)


def names_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Seconds of self time in the traced window per name of `NAMES`, once per
    process (`[bench] zaya {...}`, seconds per step).  None without a trace."""
    return trace_moe.reduced(run, "zaya", NAMES, classify)


@ts._never_raises
def mix_roofline_pct(run) -> Optional[float]:
    """The time the q|k mixing NEEDS in the traced steps on one chip over the
    device time under `cca/mix` in every direction.  Needed: its bytes
    (`builders/cca_moe_decoder.mix_bytes_per_layer`: the latent read and q, k
    written once forward, their cotangents read and the latent's written once
    backward) over the chip's HBM bandwidth, summed over the layers; the
    mixing's arithmetic (0.66 MFLOP a token and layer) is a hundredth of that
    time at the bf16 peak.  What the fusions read and write beside it (the
    kept `cca_conv1` and `cca_mixed`, float32 intermediates, the recompute) is
    time, not work: the same count whether XLA or a later kernel does it."""
    got = names_of(run)
    seconds = got["seconds"]["cca/mix"] if got else 0.0
    if seconds <= 0:
        return None
    kind, config = ts.builder(run), run["config"]
    tokens = ts.tokens_traced(run, got["steps"]) * config["num_hidden_layers"]
    needed_s = kind.mix_bytes_per_layer(config) * tokens / flops.load_peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * needed_s / seconds
