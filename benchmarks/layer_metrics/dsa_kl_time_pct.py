"""Self time of the device ops whose innermost name is `dsa/kl`: the indexer's target (the kernel `dsa_target`: one more `q k^T` a head from the saved
log-sum-exp, the heads' mean written once a tile) and the KL term with the gradient it writes, as % of the traced window: work the objective needs and
`mfu_pct` does not count. Inside `attn_core_time_pct`. `benchmarks/lib/trace_dots3.py`."""

from benchmarks.lib import trace_kind

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_kind.share_pct(run, "dsa/kl")
