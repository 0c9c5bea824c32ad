"""Self time of the device ops whose innermost `moe/*` name is `moe/combine`: the rows back into token order, the gate multiply and the sum over each token's K rows
(forward, backward and recompute), as % of the traced window, mean over the devices
(`benchmarks/lib/trace_moe.py`).  Inside `mlp_time_pct`, which counts the whole FFN block."""

from benchmarks.lib import trace_moe

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["olmoe-1chip.seq4k"]


def read(run):
    return trace_moe.share_pct(run, "moe/combine")
