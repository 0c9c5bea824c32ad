"""Needed attention FLOPs of the six WINDOW layers (32 q heads of 128, the 992 keys a query sees on average inside its window of 1,024 at 16,384
positions, forward + backward) in the traced window, over the chip's bf16 peak (197 TFLOP/s), over the flash kernels' device time under
`attn/window`: masked parts of the two 1024-key tiles a query tile visits and recomputed products are time, not work.
`benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.attn_roofline_pct(run, "attn/window", "window_attention_flops_per_token")
