"""Self time under `moe/router` + `moe/dispatch` + `moe/experts` + `moe/combine`: everything the 16 held routed experts cost (router over 64,
sort of all 131,072 assignments, the rows of the rung moved, grouped matmuls at 2304 x 896, the way back) as % of the traced window; the model
has no shared expert.  `benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.routed_share_pct(run)
