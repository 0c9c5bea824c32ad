"""The reader of `moe_rows_moved_share` (PR 48): the step counter's newest value, nothing from a program that keeps none."""

import json
import os

from benchmarks import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_the_reader_takes_the_newest_counter_and_nothing_from_a_program_without_it():
    reader = harness.load_plugin("layer_metrics", "moe_rows_moved_share")
    assert reader.read({"run_record": {"step_counters": {"moe_held_rows_mean": 0.0, "moe_rows_moved_share": 0.125}}}) == 0.125
    assert reader.read({"run_record": {"step_counters": {"moe_held_rows_mean": 0.0}}}) is None  # the parent of PR 48
    assert reader.read({"run_record": {}}) is None and reader.read({"run_record": None}) is None


def test_benchmark_json_lists_it_for_the_two_cells_that_hold_a_share():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = json.load(f)["per_layer"][-1]
    reader = harness.load_plugin("layer_metrics", "moe_rows_moved_share")
    assert entry == {"name": "moe_rows_moved_share", "unit": reader.unit, "better": "lower", "source": reader.source,
                     "layer": reader.layer, "moves": reader.moves, "workloads": reader.cells}
    assert reader.cells == ["kimi-linear-ep16-1chip.seq16k", "nemotron3-nano-ep8-1chip.seq8k"]
