"""Self time of the device ops whose innermost program scope is `layers`, the loop over the layer stack
itself and none of a layer's regions: each layer's weights (their shard, under fsdp) sliced out of the
stacked arrays, the per-layer gradients and the saved residuals written back into them, their copies.
% of the traced window, mean over the devices (`benchmarks/lib/trace_scopes.py`)."""

from benchmarks.lib import trace_scopes

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.share_pct(run, ("layers",))
