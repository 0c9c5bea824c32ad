"""Seconds of `setup_s` under the `jax::trace`, `jax::lower` and `jax::compile` spans of the STEP's program
(`fun_name` `_train_step`: `ray_tpu.models.lm.PROGRAMS`), their union: what building the train step costs a set-up."""

from benchmarks.lib import setup_record

layer = "model"
unit = "s"
source = "program_span"
moves = "setup_s"


def read(run):
    return setup_record.setup_program_s(run, "step")
