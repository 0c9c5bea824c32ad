"""Executed causal FLOPs of the `flash_bwd_dq` kernel's calls in the traced window, over the chip's
bf16 peak (197 TFLOP/s), over the calls' device time.  One call = 3 matmuls (QK^T again,
dP = dO V^T, dq = dS K) x 2*D flops per (query, key) pair x S*S/2 causal pairs x local heads x
local batch: 3/6 of the layer's needed forward + backward count in `benchmarks/lib/flops.py`.
QK^T and dP are computed here AND in `flash_bwd_dkv`: executed, so counted.  Compute-bound."""

from benchmarks.lib import trace_scopes

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.kernel_roofline_pct(run, "flash_bwd_dq")
