"""Self time of the device ops whose innermost program scope is `layer/mlp`: `ln2`, the gate/up/down
einsums, the residual add (forward, backward and
recompute), as % of the traced window, mean over the devices (`benchmarks/lib/trace_scopes.py`)."""

from benchmarks.lib import trace_scopes

layer = "model"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.share_pct(run, ("layer/mlp",))
