"""The pairs the training mask holds (S^2 + S * B) as % of the pairs of the tiles the flash FORWARD visits for it, from the block sizes in use
(`ray_tpu/ops/pallas/flash_attention.py` `diffusion_mask_fill_pct`; 80.04% at 8,192 tokens, blocks of 4, tiles of 1024: 80 of a head's 256 tile
pairs), as the run's record keeps the program's step counter `attn_diffusion_mask_fill_pct`.  `benchmarks/lib/trace_sdar.py`."""

from benchmarks.lib import trace_sdar

layer = "attention"
unit = "%"
source = "program_counter"
moves = "tokens_per_s_per_chip"
cells = ["sdar-ep8-1chip.seq8k"]


def read(run):
    return trace_sdar.counter(run, "attn_diffusion_mask_fill_pct")
