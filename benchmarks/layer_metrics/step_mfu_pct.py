"""The whole step's share of the chip's bf16 peak, read from INSIDE: the run record's `steps.tokens_per_step`
(the global batch's tokens as `LMTrainContext.train_step` counted them) ÷ chips ÷ the median `period_s` of the
window's rows × the builder's `needed_flops_per_token(config, seq_len)` ÷ `peaks.json`'s peak.  The same
needed FLOPs as the end-to-end `mfu_pct`, with the program's clock and count in the place of the loop's."""

from benchmarks.lib import step_rows

layer = "device"
unit = "%"
source = "program_counter"
moves = "tokens_per_s_per_chip"


def read(run):
    return step_rows.mfu_pct(run)
