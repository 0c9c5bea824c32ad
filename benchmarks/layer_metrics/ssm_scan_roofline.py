"""Needed FLOPs of the selective scan (chunked form at the published chunk 256, causal half, forward
+ backward) in the traced window, over the chip's bf16 peak (197 TFLOP/s), over the device time under
`ssm/scan` in every direction: recompute is time, not work.  Against the compute peak: ~300 FLOP per
byte of x, B, C, dt, y by its needed counts.  `benchmarks/lib/trace_ssm.py`."""

from benchmarks.lib import trace_ssm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["granite-h-micro-1chip.seq8k"]


def read(run):
    return trace_ssm.scan_roofline_pct(run)
