"""Seconds of `setup_s` under the spans of the INIT program (`fun_name` `_init`: the parameters and the
optimizer's state), less what the step's and the apply's already cover."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.setup_program_s(run, "init")
