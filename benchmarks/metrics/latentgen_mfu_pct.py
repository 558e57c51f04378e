"""Model step, the whole step of a latent-attention model with shared
and held routed experts: FLOPs a processed token from shapes
(``lobench/counts_mla.py``: the projections and the absorbed attention
over the mean number of keys the window's slot-steps attended, router,
shared expert, the held experts' rows by the program's own count
``expert_rows`` a processed token over the traced turns, the dense
layer, the head) times processed tokens a second, over the chip's bf16
peak."""

from lobench import counts_mla, latent_turns


def read(record, run):
    win, turns = record.get("window"), latent_turns.read(run)
    if not win or not win["processed_tokens"] or not turns \
            or not turns["tokens"]:
        return None
    flops = counts_mla.forward_flops_per_token(
        run.cp, win["mean_keys"], turns["expert_rows"] / turns["tokens"]
    )
    rate = win["processed_tokens"] / win["seconds"]
    return 100.0 * flops * rate / run.peaks["flops_bf16"]
