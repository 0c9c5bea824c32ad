"""Self time of the device ops whose innermost `moe/*` name is `moe/experts`: the three grouped matmuls (gate, up, down) and `silu(gate) * up`
(forward, backward and recompute), as % of the traced window, mean over the devices
(`benchmarks/lib/trace_moe.py`).  Inside `mlp_time_pct`, which counts the whole FFN block."""

from benchmarks.lib import trace_moe

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["olmoe-1chip.seq4k"]


def read(run):
    return trace_moe.share_pct(run, "moe/experts")
