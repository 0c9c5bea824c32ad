"""Seconds of `setup_s` under the record's `jax::lower` events: jaxpr to StableHLO of every program the
set-up builds."""

from benchmarks.lib import run_record

layer = "model"
unit = "s"
source = "program_counter"
moves = "setup_s"


def read(run):
    return run_record.setup_s_under(run, "jax::lower")
