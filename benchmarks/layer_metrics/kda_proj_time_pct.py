"""Self time of the device ops whose innermost name is `kda/proj`: a KDA layer's ln1, fused q|k|v projection, both low-rank gates, beta,
`wo` and residual add (forward, backward and recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_kimi.py`).
Inside `attn_proj_time_pct`, which counts that half of every kind of mixer."""

from benchmarks.lib import trace_kimi

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k"]


def read(run):
    return trace_kimi.share_pct(run, "kda/proj")
