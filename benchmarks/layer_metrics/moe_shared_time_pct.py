"""Self time of the device ops whose innermost name is `moe/shared`: the shared expert's SwiGLU that every token goes through
(forward, backward and recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_kimi.py`).  Inside `mlp_time_pct`."""

from benchmarks.lib import trace_kimi

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k"]


def read(run):
    return trace_kimi.share_pct(run, "moe/shared")
