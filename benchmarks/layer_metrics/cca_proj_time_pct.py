"""Self time of the device ops whose innermost name is `cca/proj`: a CCA layer's projections between the stream and its latents (ln1, `W_Q` to 1024,
`W_K` and `W_V` to 256 each with the value's one-position shift, and at the layer's end `W_O` and the join through the learned residual scaling),
forward, backward and recompute, as % of the traced window. Inside `attn_proj_time_pct`. `benchmarks/lib/trace_zaya.py`."""

from benchmarks.lib import trace_kind

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_kind.share_pct(run, "cca/proj")
