"""Self time of the device ops whose innermost name is `s6/proj`: a Mamba-1 layer's ln1, `W_in`, `W_x`, `W_dt` with its softplus, `W_out`
and residual add (forward, backward and recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_sambay.py`).
Inside `attn_proj_time_pct`, which counts that half of every kind of mixer."""

from benchmarks.lib import trace_sambay

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.share_pct(run, "s6/proj")
