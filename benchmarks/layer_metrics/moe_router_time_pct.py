"""Self time of the device ops whose innermost `moe/*` name is `moe/router`: the router matmul (float32), its softmax and top-k, and the statistics of the router losses
(forward, backward and recompute), as % of the traced window, mean over the devices
(`benchmarks/lib/trace_moe.py`).  Inside `mlp_time_pct`, which counts the whole FFN block."""

from benchmarks.lib import trace_moe

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["olmoe-1chip.seq4k"]


def read(run):
    return trace_moe.share_pct(run, "moe/router")
