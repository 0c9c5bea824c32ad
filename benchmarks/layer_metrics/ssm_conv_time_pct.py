"""Self time of the device ops whose innermost `ssm/*` name is `ssm/conv`: a Mamba-2 layer's causal convolution + SiLU, the softplus of dt and the gated RMSNorm, the bandwidth-bound part
(forward, backward and recompute), as % of the traced window, mean over the devices
(`benchmarks/lib/trace_ssm.py`).  Inside `attn_proj_time_pct`, which counts that half of both kinds of mixer."""

from benchmarks.lib import trace_ssm

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["granite-h-micro-1chip.seq8k"]


def read(run):
    return trace_ssm.share_pct(run, "ssm/conv")
