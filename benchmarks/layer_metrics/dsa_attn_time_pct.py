"""Self time of the device ops whose innermost name is `dsa/attn`: the attention core over the selected keys, both directions (the kernels
`dsa_attn_fwd`, `dsa_attn_bwd_dq`, `dsa_attn_bwd_dkv` at 512 x 512 tiles with the mask's tile as one more operand, the scaling of q and the transposes
around them), as % of the traced window. Inside `attn_core_time_pct`. `benchmarks/lib/trace_dots3.py`."""

from benchmarks.lib import trace_kind

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_kind.share_pct(run, "dsa/attn")
