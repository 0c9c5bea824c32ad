"""Needed FLOPs of the gated delta rule with ONE decay a head (chunked form at chunk 64, causal half, forward + backward: `3 * 32 * (64 * 640 + 6 * 128 * 128)` a token and
layer) in the traced window, over the chip's bf16 peak (197 TFLOP/s), over the device time under `gdn/scan` in every direction: recompute, and what a per-channel kernel does
beyond the scalar rule, is time, not work.  `benchmarks/lib/trace_qwen3_next.py`, `benchmarks/builders/qwen3_next_decoder.py` `gdn_scan_flops_per_token`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.gdn_scan_roofline_pct(run)
