"""Median of the program's own `train_step/make_batch` span (`tracing.annotate` in
`LMTrainContext.train_step`, on the profiler's clock) in the traced steps: the host batch sharded onto
the mesh, one `make_array_from_callback` callback per device."""

from benchmarks.lib import trace_scopes

layer = "train step host side"
unit = "ms"
source = "program_span"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_scopes.program_span_ms(run, "train_step/make_batch")
