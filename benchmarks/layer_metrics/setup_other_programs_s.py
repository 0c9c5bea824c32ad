"""Seconds of `setup_s` under the spans of every OTHER program (the plain reference's blocks, the one-op programs
of eager calls: `convert_element_type`, `dynamic_slice`, ...), less what the three named programs already cover."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.setup_program_s(run, "other")
