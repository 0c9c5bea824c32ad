"""Seconds of `setup_s` under the spans of the APPLY program (`fun_name` `_forward`: the reference check calls the
program's own `apply`, a second whole trace, lowering and load of the model), less what the step's already cover."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.setup_program_s(run, "apply")
