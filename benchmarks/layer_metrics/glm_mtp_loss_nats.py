"""The multi-token-prediction module's cross entropy, unweighted: `mtp_loss` of the program's step metrics (the mean over the 8,191 positions that have a token
after the next), the newest value the run's record keeps: the second term of the objective is alive and falls with the first.  ln 19,360 = 9.87 at the
start.  `benchmarks/lib/trace_glm.py`."""

from benchmarks.lib import trace_glm

layer = "model"
unit = "nats"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["glm47-flash-ep8-1chip.seq8k"]


def read(run):
    return trace_glm.counter(run, "mtp_loss")
