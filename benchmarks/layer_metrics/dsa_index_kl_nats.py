"""The indexer's KL term, the sum over the full layers of `mean_t KL(p_t || softmax_{S_t}(I[t, .]))`: `dsa_index_kl` of the program's step metrics, the
nats the objective adds to the cross entropy it is reported apart from, the newest value the run's record keeps: the second term of the objective is
alive and falls as the indexer learns the attention it selects for. `benchmarks/lib/trace_dots3.py`."""

from benchmarks.lib import trace_kind

layer = "attention"
unit = "nats"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_kind.counter(run, "dsa_index_kl")
