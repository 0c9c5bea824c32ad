"""Area of the key tiles the WINDOWED flash forward visits as % of what a causal call visits at its own tiles, from the block sizes in use
(`ray_tpu/ops/pallas/flash_attention.py` `window_tiles_visited_pct`; 22.8% at 16,384 tokens, window 1,024, tiles of 1024: 31 of 136), as the
run's record keeps the program's step counter `attn_window_tiles_visited_pct`.  Needed is 12.1%.  `benchmarks/lib/trace_mellum.py`."""

from benchmarks.lib import trace_mellum

layer = "attention"
unit = "%"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["mellum2-ep4-1chip.seq16k"]


def read(run):
    return trace_mellum.counter(run, "attn_window_tiles_visited_pct")
