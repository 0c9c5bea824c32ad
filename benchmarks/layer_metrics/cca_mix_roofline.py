"""The time the q|k mixing NEEDS in the traced window (its bytes over 819 GB/s: the [1280]-wide latent read and q, k written once forward, their
cotangents read and the latent's written once backward, bf16, five layers; `builders/cca_moe_decoder.mix_bytes_per_layer`) over the device time
under `cca/mix` in every direction: the kept intermediates, the float32 passes between the fusions and the recompute are time, not work, so the
count is the same whether XLA or a later kernel does the mixing. `benchmarks/lib/trace_zaya.py`."""

from benchmarks.lib import trace_zaya

layer = "attention"
unit = "%"
source = "device_trace"
moves = "tokens_per_s_per_chip"


def read(run):
    return trace_zaya.mix_roofline_pct(run)
