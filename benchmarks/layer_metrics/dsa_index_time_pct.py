"""Self time of the device ops whose innermost name is `dsa/index`: a full layer's indexer, its three projections (q^I from the q latent, the one key a
position with its LayerNorm, the per-head weights), the rope on their first 64 dims and the scores `sum_j w relu(q^I . k^I)` over every pair (plain
XLA, blocks of 256 queries: [256, 64, 8192] float32 products a block), forward, backward and recompute, as % of the traced window. Inside
`attn_core_time_pct`. `benchmarks/lib/trace_dots3.py`."""

from benchmarks.lib import trace_kind

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_kind.share_pct(run, "dsa/index")
