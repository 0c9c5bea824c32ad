"""Median `period_s` of the window's rows of the run record's `steps` (`steps.rows[*].period_s`): the train
step's period as the library's own `StepClock` has it, `train_step` entry to entry on the stepping thread,
every step of the window, traced or not.  The program's own denominator of tokens per second; the loop's
median step (loss fetch to loss fetch) is its outside twin."""

from benchmarks.lib import step_rows

layer = "train step host side"
unit = "ms"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return step_rows.read(run, "period_ms")
