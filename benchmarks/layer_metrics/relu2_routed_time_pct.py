"""Self time under `moe/dispatch` + `moe/experts` + `moe/combine`: what the 16 held routed experts cost behind the router (sort and gather of
all 49,152 assignments, the grouped matmuls at 2688 x 1856, the un-permute) as % of the traced window.
`benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.share_pct(run, *trace_nemotron_h.ROUTED)
