"""Self time under `ssm/conv` (the convolution + SiLU over 6,144 channels: the kernels `ssm_conv_fwd` / `ssm_conv_bwd`; softplus; the gated
RMSNorm per group of 512) as % of the traced window.  `benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.share_pct(run, "ssm/conv")
