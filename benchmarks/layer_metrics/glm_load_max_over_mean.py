"""The busiest expert's assignments over the mean of all 64, in the worst layer (the module's block counted): `moe_load_max_over_mean` of the step metrics, the
newest value the run's record keeps.  1 at perfect balance, 64 / 4 = 16 when every token of a layer chooses the same four.  `benchmarks/lib/trace_glm.py`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "ratio"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.counter(run, "moe_load_max_over_mean")
