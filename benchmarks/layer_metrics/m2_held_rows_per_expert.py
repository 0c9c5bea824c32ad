"""Rows one held expert multiplied in a step: `moe_held_rows_mean` of the program's step metrics (mean over the 16 held experts of the eight
layers), the newest value the run's record keeps.  2,048 under a uniform router at 16,384 tokens, 8 of 64.
`benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "model"
unit = "rows"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.counter(run, "moe_held_rows_mean")
