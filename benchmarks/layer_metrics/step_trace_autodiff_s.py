"""Self seconds of the path `autodiff` itself: what `jax.value_and_grad` runs after `_loss` returns (transposition,
partial evaluation, pytrees), less the named regions the backward pass enters."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.step_trace_s(run, "autodiff")
