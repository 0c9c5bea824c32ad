"""Self time of the device ops whose innermost name is `diff/combine`: differential attention's `a1 - lambda a2`, its RMSNorm over 128 and scale,
float32 from the kernels' outputs (forward, backward and recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_sambay.py`).
Inside `attn_core_time_pct`."""

from benchmarks.lib import trace_sambay

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.share_pct(run, "diff/combine")
