"""Self time of the device ops whose innermost name is `s6/conv`: a Mamba-1 layer's causal depthwise convolution + SiLU over 5,120 channels (on TPU
the kernels `ssm_conv_fwd` / `ssm_conv_bwd`) and the gate `y * silu(z)` (forward, backward and recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_sambay.py`).
Inside `attn_proj_time_pct`."""

from benchmarks.lib import trace_sambay

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.share_pct(run, "s6/conv")
