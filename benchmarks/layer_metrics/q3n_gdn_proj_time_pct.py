"""Self time of the device ops whose innermost name is `gdn/proj`: a delta layer's ln1, its fused q|k|v|z projection (2048 -> 12,288), beta's and the decay's logits,
`wo` and the residual add (forward, backward and recompute), as % of the traced window (`benchmarks/lib/trace_qwen3_next.py`).  Inside `attn_proj_time_pct`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.share_pct(run, "gdn/proj")
