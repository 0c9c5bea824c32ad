"""Median of the loop's `report` span (`train.report` of one step's dict) over the window."""

import statistics

layer = "session and report"
unit = "ms"
source = "program_span"
moves = "tokens_per_s_per_chip"


def read(run):
    spans = run["summary"]["host_spans"]
    if not spans:
        return None
    return 1e3 * statistics.median(s[3] for s in spans)
