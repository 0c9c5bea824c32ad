"""Self seconds of the step's trace under every other named path: the scans' machinery under `layers`, the mixers,
FFNs and experts, in the forward trace or entered again from the backward pass (`autodiff/<name>`)."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.step_trace_s(run, "stack")
