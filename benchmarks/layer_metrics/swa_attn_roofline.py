"""Needed attention FLOPs of the two WINDOWED differential layers (both maps, the keys inside the 512 window, forward + backward) in the traced
window, over the chip's bf16 peak (197 TFLOP/s), over the flash kernels' device time under `diff/window`: masked halves of tiles and recomputed
products are time, not work.  `benchmarks/lib/trace_sambay.py`."""

from benchmarks.lib import trace_sambay

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.swa_attn_roofline_pct(run)
