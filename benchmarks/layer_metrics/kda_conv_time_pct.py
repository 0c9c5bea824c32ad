"""Self time of the device ops whose innermost name is `kda/conv`: a KDA layer's three causal convolutions + SiLU (one call), the L2 norms of
q and k, the decay's softplus, beta's sigmoid and the gated per-head RMSNorm, the bandwidth-bound part (forward, backward and recompute), as %
of the traced window, mean over the devices (`benchmarks/lib/trace_kimi.py`).  Inside `attn_proj_time_pct`."""

from benchmarks.lib import trace_kimi

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k"]


def read(run):
    return trace_kimi.share_pct(run, "kda/conv")
