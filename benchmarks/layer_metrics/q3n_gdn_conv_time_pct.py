"""Self time of the device ops whose innermost name is `gdn/conv`: a delta layer's width-4 causal convolution + SiLU over 8,192 channels (on TPU Mamba-2's kernels), the
L2 norms of q and k, the decay's softplus and the gated per-head RMSNorm (every direction), as % of the traced window (`benchmarks/lib/trace_qwen3_next.py`)."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.share_pct(run, "gdn/conv")
