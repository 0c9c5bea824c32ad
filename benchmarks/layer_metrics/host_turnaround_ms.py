"""Median host time from a step's loss fetch returning to the next step's dispatch
returning: `report` + `data_next` + `make_batch+dispatch`.  The device has nothing
queued in that time, so it is the floor of the idle gap between steps."""

import statistics

layer = "train step host side"
unit = "ms"
source = "program_span"
moves = "tokens_per_s_per_chip"


def read(run):
    spans = run["summary"]["host_spans"]
    if len(spans) < 2:
        return None
    return 1e3 * statistics.median(a[3] + b[0] + b[1] for a, b in zip(spans, spans[1:]))
