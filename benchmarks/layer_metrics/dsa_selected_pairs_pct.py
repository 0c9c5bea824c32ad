"""The (query, key) pairs a full layer's core attended as % of the causal pairs: the program's step counters `dsa_selected_pairs` (the sum of the
selection's mask, the mean over the full layers) over `dsa_causal_pairs` (S (S + 1) / 2), the newest values the run's record keeps: 43.75 at 8,192
positions and top-2048 (`min(t + 1, 2048)` a query), more only where scores tie. `benchmarks/lib/trace_dots3.py`."""

from benchmarks.lib import trace_kind

layer = "attention"
unit = "%"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    selected, causal = trace_kind.counter(run, "dsa_selected_pairs"), trace_kind.counter(run, "dsa_causal_pairs")
    return None if selected is None or not causal else 100.0 * selected / causal
