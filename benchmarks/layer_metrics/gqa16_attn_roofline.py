"""Needed causal attention FLOPs of the ONE attention block (`6 * S * 32 * 128` a token: 32 q heads of 128 over 2 K/V heads, a 16:1 repeat) in
the traced window, over the chip's bf16 peak, over the three flash kernels' device time: the second forward call and the backward kernels'
recomputed products are time, not work.  `benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.attn_roofline_pct(run)
