"""Self time of the device ops of direction `recompute`, whatever their scope: ops under
`jax.checkpoint`'s `rematted_computation` and the forward flash kernel run a second time in the
backward pass.  % of the traced window, mean over the devices (`benchmarks/lib/trace_scopes.py`)."""

from benchmarks.lib import trace_scopes

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.share_pct(run, None, ("recompute",))
