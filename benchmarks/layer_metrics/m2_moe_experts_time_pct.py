"""Self time under `moe/experts`: the grouped matmuls of the 16 held experts at 2304 x 896 and the activation between them, every direction, as
% of the traced window: the part of `m2_moe_routed_time_pct` that follows the rows the router gave (`m2_experts_roofline` divides the same
seconds).  `benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.routed_share_pct(run, ("moe/experts",))
