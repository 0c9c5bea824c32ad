"""Needed FLOPs of the selective scan in its GROUPED chunked form at the published chunk 128 (`C B^T` once a group;
`builders/nemotron_h_decoder.ssd_flops_per_token`), forward + backward, over the chip's bf16 peak (197 TFLOP/s), over the device time under
`ssm/scan` in every direction: recompute is time, not work.  `benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.scan_roofline_pct(run)
