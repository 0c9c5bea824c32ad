"""Self time under `moe/shared`: the shared two-matrix relu2 expert of width 3712 that every token goes through (forward, backward, recompute)
as % of the traced window. Inside `mlp_time_pct`.  `benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.share_pct(run, "moe/shared")
