"""Needed causal attention FLOPs of the ONE latent-attention layer (`3 * S * 32 * (192 + 128)` a token, forward + backward) in the
traced window, over the chip's bf16 peak (197 TFLOP/s), over the three flash kernels' device time: the second forward call and the backward
kernels' recomputed products are time, not work.  `benchmarks/lib/trace_kimi.py`."""

from benchmarks.lib import trace_kimi

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k"]


def read(run):
    return trace_kimi.mla_attn_roofline_pct(run)
