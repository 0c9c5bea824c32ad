"""Self time of the device ops under `layer/attn_core` (the K/V repeat, transposes, `delta` and the three
flash kernels inside it), as % of the traced window, mean over the devices.  Minus
`attn_kernel_time_pct` it is the XLA glue around the kernels (`benchmarks/lib/trace_scopes.py`)."""

from benchmarks.lib import trace_scopes

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.share_pct(run, ("layer/attn_core",) + trace_scopes.KERNELS)
