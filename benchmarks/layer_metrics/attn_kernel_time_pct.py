"""Summed device time of the Mosaic flash kernels (the compiled step's
`tpu_custom_call` instructions, found by name in the trace) over the traced
window, mean over the devices."""

from benchmarks.lib.trace_reduce import mean_share_pct

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return mean_share_pct(run.get("trace"), "kernel_s")
