"""Model step, the whole step: FLOPs a processed token (prompt or
output, LM head included) from shapes, at the mean number of keys the
window's slot-steps attended, times processed tokens a second, over
the chip's bf16 peak."""

from lobench import counts


def read(record, run):
    win = record.get("window")
    if not win or not win["processed_tokens"]:
        return None
    flops = counts.decoder_forward_flops_per_token(
        run.cp, win["mean_keys"]
    )
    rate = win["processed_tokens"] / win["seconds"]
    return 100.0 * flops * rate / run.peaks["flops_bf16"]
