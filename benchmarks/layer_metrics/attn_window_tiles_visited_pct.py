"""Area of the key tiles the WINDOWED flash forward visits as % of what a causal call visits at its own tiles, from the block sizes in use
(`ray_tpu/ops/pallas/flash_attention.py` `window_tiles_visited_pct`; 21.5% at 8,192 tokens, window 512, tiles of 512 against 1024), as the
run's record keeps the program's step counter.  Needed is 6.1%.  `benchmarks/lib/trace_sambay.py`."""

from benchmarks.lib import trace_sambay

layer = "attention"
unit = "%"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["phi4-mini-flash-1chip.seq8k"]


def read(run):
    return trace_sambay.window_tiles_visited_pct(run)
