"""The busiest expert's assignments over the mean of all 512, in the worst layer: `moe_load_max_over_mean` of the step metrics, the newest value the run's record keeps.  1 at
perfect balance, 512 / 10 = 51.2 when every token of a layer chooses the same ten.  `benchmarks/lib/trace_qwen3_next.py`."""

from benchmarks.lib import trace_qwen3_next

layer = "model"
unit = "ratio"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["qwen3-next-ep16-1chip.seq8k"]


def read(run):
    return trace_qwen3_next.counter(run, "moe_load_max_over_mean")
