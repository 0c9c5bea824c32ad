"""Self time under `moe/combine`: the held experts' rows scattered back to their tokens and summed with the gate values (and the gradient's
gather), as % of the traced window: it moves the rung's rows as `m2_moe_dispatch_time_pct` does, whatever the experts really hold.
`benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.routed_share_pct(run, ("moe/combine",))
