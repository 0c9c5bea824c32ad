"""Needed causal attention FLOPs of the two FULL layers (`6 * S * 32 * 128` a token and layer, forward + backward) in the traced window, over the
chip's bf16 peak, over the flash kernels' device time under `attn/full`: the second forward call and the backward kernels' recomputed products
are time, not work.  `benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.attn_roofline_pct(run, "attn/full", "full_attention_flops_per_token")
