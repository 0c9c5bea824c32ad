"""Self time of the device ops under NONE of the program's scopes or kernel names (no name in the op's
path, or no path at all), as % of the traced window, mean over the devices: what the per-scope
metrics cannot see, the coverage guard of all of them (`benchmarks/lib/trace_scopes.py`)."""

from benchmarks.lib import trace_scopes

layer = "device"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.share_pct(run, (trace_scopes.UNSCOPED,))
