"""The busiest expert's assignments over the mean of all 128, in the worst expert block: `moe_load_max_over_mean` of the step metrics, the
newest value the run's record keeps. 1 at perfect balance, 128 / 6 = 21.3 when every token of a block chooses the same six.
`benchmarks/lib/trace_nemotron_h.py`."""

from benchmarks.lib import trace_nemotron_h

layer = "model"
unit = "ratio"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    return trace_nemotron_h.counter(run, "moe_load_max_over_mean")
