"""Self time under `moe/dispatch`: the sort of all 131,072 assignments of a layer and the rows of its rung gathered for the 16 held experts
(forward, and the way back of the gradient), as % of the traced window: one of the three parts of `m2_moe_routed_time_pct` a later PR can move
alone (the rung is 65,536 rows or all 131,072 for the ~13-26k a layer holds).  `benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.routed_share_pct(run, ("moe/dispatch",))
