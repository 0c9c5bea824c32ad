"""Self time of the device ops whose innermost name is `gmu`: a Gated Memory Unit's ln1, `W_1`, the gate on the memory, `W_2` and residual add
(forward, backward and recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_sambay.py`).
Inside `attn_proj_time_pct` (the layer has no core)."""

from benchmarks.lib import trace_sambay

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.share_pct(run, "gmu")
