"""Executed causal FLOPs of the `flash_bwd_dkv` kernel's calls in the traced window, over the
chip's bf16 peak (197 TFLOP/s), over the calls' device time.  One call = 4 matmuls (QK^T again,
dV = P^T dO, dP = dO V^T, dk = dS^T Q) x 2*D flops per (query, key) pair x S*S/2 causal pairs x
local heads x local batch: 4/6 of the layer's needed count in `benchmarks/lib/flops.py`.  The three
kernels run 2+2+3+4 = 11 matmuls per pair where a step needs 6, so their rooflines weighted by
their times, times 6/11, give `attn_kernel_roofline`.  Compute-bound at head size 128."""

from benchmarks.lib import trace_scopes

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.kernel_roofline_pct(run, "flash_bwd_dkv")
