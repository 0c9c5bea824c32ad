"""Stall events of the run's record whose step lies in the window: steps whose period, entry to entry of
`train_step`, was over twice the median of the last 4,096.  What the median step time hides."""

from benchmarks.lib import run_record

layer = "train step host side"
unit = "count"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return run_record.stalls_in_window(run)
