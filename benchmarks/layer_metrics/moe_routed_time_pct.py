"""Self time of the device ops whose innermost name is one of `moe/router`, `moe/dispatch`, `moe/experts`, `moe/combine`: everything the
ROUTED experts cost a layer that holds a share of them, whose dispatch still sorts and gathers all T*K assignments for the ~T*K/16 rows it
multiplies (forward, backward and recompute), as % of the traced window (`benchmarks/lib/trace_kimi.py`).  Inside `mlp_time_pct`."""

from benchmarks.lib import trace_kimi

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k"]


def read(run):
    return trace_kimi.share_pct(run, *trace_kimi.ROUTED)
