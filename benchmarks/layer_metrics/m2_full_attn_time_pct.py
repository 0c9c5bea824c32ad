"""Self time under `attn/full`, every direction: the flash kernels of the two full-causal layers and what is around them in the core (the 8:1
K/V repeat), as % of the traced window.  `benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.attn_share_pct(run, "attn/full")
