"""Seconds of `setup_s` under the record's `jax::compile` events: XLA's compile on a cache miss; on a hit
the read of the persistent cache and the executable's load onto the chip."""

from benchmarks.lib import run_record

layer = "model"
unit = "s"
source = "program_counter"
moves = "setup_s"


def read(run):
    return run_record.setup_s_under(run, "jax::compile")
