"""Model step, the whole step of a model whose every layer runs a
Mamba-2 mixer beside attention: FLOPs a processed token from shapes
(``lobench/counts_hybrid.py``: the mixer's projections, convolution and
recurrence, attention's projections and its two products at the
window's mean keys, the FFN, the head) times the processed tokens a
second the clients saw in the window (prompt and output alike: a prompt
token costs the step what an output token does), over the chip's bf16
peak.  Low by nature: the step is bound by the bytes of weights and
states."""

from lobench import counts_hybrid


def read(record, run):
    win = record.get("window")
    if not win or not win["processed_tokens"]:
        return None
    rate = win["processed_tokens"] / win["seconds"]
    return 100.0 * counts_hybrid.forward_flops_per_token(
        run.cp, win["mean_keys"]
    ) * rate / run.peaks["flops_bf16"]
