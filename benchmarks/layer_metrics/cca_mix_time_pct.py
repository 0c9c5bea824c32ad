"""Self time of the device ops whose innermost name is `cca/mix`: what CCA does to the q|k latent ALONG THE SEQUENCE before the softmax (the
depthwise causal convolution, the grouped one of a [128, 128] map a head and tap, the q-k mean, the unit norm of each head, tau, the rope on 64 of
128 dims; plain XLA), forward, backward and recompute, as % of the traced window. Inside `attn_proj_time_pct`. `benchmarks/lib/trace_zaya.py`."""

from benchmarks.lib import trace_kind

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_kind.share_pct(run, "cca/mix")
