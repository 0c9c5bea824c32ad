"""`setup_s` less the union of every `jax::trace` / `jax::lower` / `jax::compile` span in it: execution on the device
and Python that builds no program.  The coverage guard of the four `setup_*_program(s)_s`: the five add up to `setup_s`."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.setup_program_s(run, "outside_jax")
