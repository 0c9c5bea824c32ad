"""Needed FLOPs of the KDA recurrence (chunked form at chunk 64, causal half, forward + backward) in the traced window, over the chip's
bf16 peak (197 TFLOP/s), over the device time under `kda/scan` in every direction: recompute is time, not work.  Against the compute peak.
`benchmarks/lib/trace_kimi.py`, `benchmarks/builders/kimi_linear_decoder.py` `kda_scan_flops_per_token`."""

from benchmarks.lib import trace_kimi

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k"]


def read(run):
    return trace_kimi.kda_scan_roofline_pct(run)
