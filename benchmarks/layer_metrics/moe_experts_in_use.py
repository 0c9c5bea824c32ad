"""The experts of a layer that got a row in the step, mean over the expert layers: `moe_experts_in_use` of the program's step metrics, which a
job whose stored router bias follows the load reports (`TransformerConfig.router_bias_update_rate`, `models/moe.py` `router_losses`), the newest
value the run's record keeps. 16 of 16 is what the rule keeps up in `zaya1`; a collapsed router reads 1-3, and its grouped matmuls then pass
over fewer 512-row tiles (32 a layer where sixteen experts in use run 40-47), so the step is SHORTER the lower this reads. Reached through
the run's kind (`benchmarks/lib/trace_kind.py`)."""

from benchmarks.lib import trace_kind

layer = "model"
unit = "experts"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_kind.counter(run, "moe_experts_in_use")
