"""Self time of the device ops whose innermost name is `ssm/proj` (the block's norm, `in_proj` to 10,304 columns, `out_proj`, the residual add;
forward, backward and recompute) as % of the traced window, in the four Mamba-2 blocks of Nemotron-3-Nano's period.
`benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.share_pct(run, "ssm/proj")
