"""Needed causal attention FLOPs of the full differential layer and the two cross layers (`3 * S * 40 * (64 + 128)` a token and layer, forward +
backward) in the traced window, over the chip's bf16 peak (197 TFLOP/s), over the flash kernels' device time under `diff/full`.
`benchmarks/lib/trace_sambay.py`."""

from benchmarks.lib import trace_sambay

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.full_attn_roofline_pct(run)
