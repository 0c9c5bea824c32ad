"""Needed FLOPs of the expert matmuls (three grouped matmuls per layer, K experts per token,
forward + backward) in the traced window, over the chip's bf16 peak (197 TFLOP/s), over the
device time under `moe/experts` in every direction: recompute is time, not work.  Compute-bound
(~1,024 rows per expert).  `benchmarks/lib/trace_moe.py`."""

from benchmarks.lib import trace_moe

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["olmoe-1chip.seq4k"]


def read(run):
    return trace_moe.experts_roofline_pct(run)
