"""The busiest expert's assignments over the mean of all 64, in the worst layer: `moe_load_max_over_mean` of the step metrics, the newest value
the run's record keeps.  1 at perfect balance, 64 / 8 = 8 when every token of a layer chooses the same eight.
`benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "model"
unit = "ratio"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.counter(run, "moe_load_max_over_mean")
