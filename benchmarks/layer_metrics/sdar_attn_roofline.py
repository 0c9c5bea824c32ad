"""Needed attention FLOPs under the block-diffusion mask (`12 * (S + B) * 32 * 128` a data token and layer: the mask's S^2 + S * B true pairs,
forward + backward; `builders/block_diffusion_moe_decoder.attention_flops_per_token`) in the traced window, over the chip's bf16 peak (197
TFLOP/s), over the flash kernels' device time under `attn/block_diffusion`: the masked fifth of the visited tiles, the second forward call and
the backward kernels' recomputed products are time, not work.  Compute-bound at head size 128.  `benchmarks/lib/trace_sdar.py`."""

from benchmarks.lib import trace_sdar

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"
cells = ["sdar-ep8-1chip.seq8k"]


def read(run):
    return trace_sdar.attn_roofline_pct(run)
