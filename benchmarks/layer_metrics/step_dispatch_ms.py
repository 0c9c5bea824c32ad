"""Median of the program's own `train_step/dispatch` span (`tracing.annotate` in
`LMTrainContext.train_step`, on the profiler's clock) in the traced steps: the call of the jitted step
until it returns (the enqueue, not the step)."""

from benchmarks.lib import trace_scopes

layer = "train step host side"
unit = "ms"
source = "program_span"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.program_span_ms(run, "train_step/dispatch")
