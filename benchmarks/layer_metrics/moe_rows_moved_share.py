"""Rows a share of the experts MOVED over the T x K assignments it sorted: `moe_rows_moved_share` of the program's step metrics (the rung
its row buffers took over T x K, mean over the expert layers: `ray_tpu/models/moe.py` `_rungs`), the newest value the run's record keeps
(`step_counters`).  1.0 is every assignment gathered, multiplied and brought back whatever is held (a program from before PR 48 keeps no
such counter: nothing then); 0.125 / 0.25 the lowest rung of the two cells, twice a uniform router's share."""

from benchmarks.lib import run_record

layer = "model"
unit = "ratio"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["kimi-linear-ep16-1chip.seq16k", "nemotron3-nano-ep8-1chip.seq8k"]


def read(run):
    counters = (run_record.record_of(run) or {}).get("step_counters") or {}
    return counters.get("moe_rows_moved_share")
