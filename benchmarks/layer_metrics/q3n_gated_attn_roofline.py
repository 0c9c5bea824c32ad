"""Needed causal attention FLOPs of the TWO attention layers at 16 heads of 256 (`6 * S * 16 * 256` a token and layer, forward + backward) in the traced window, over the
chip's bf16 peak (197 TFLOP/s), over the three flash kernels' device time: the second forward call and the backward kernels' recomputed products are time, not work.
`benchmarks/lib/trace_qwen3_next.py`."""

from benchmarks.lib import trace_qwen3_next

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.gated_attn_roofline_pct(run)
