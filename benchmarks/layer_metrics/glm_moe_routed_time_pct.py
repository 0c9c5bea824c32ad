"""Self time under `moe/router` + `moe/dispatch` + `moe/experts` + `moe/combine`, stack and module: everything the 16 held routed experts cost (router over 64,
sort of all 32,768 assignments, the rows of the rung moved, grouped matmuls at 2048 x 1536, the way back) as % of the traced window
(`benchmarks/lib/trace_glm.py`).  Inside `mlp_time_pct`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.share_pct(run, *trace_glm.ROUTED)
