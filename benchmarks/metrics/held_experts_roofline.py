"""Kernels (``ops/moe.py`` ``grouped_matmul`` over a held share of the
experts): the least time the chip could take for the three grouped
matmuls a routed layer over the traced steps (their FLOPs over the rows
that reached a held expert, or the bytes of the held experts reached
and of those rows, whichever is longer) over the summed device time of
the custom calls the trace names ``gmm`` (jax's megablox kernel) or
``ragged-dot-none`` (``jax.lax.ragged_dot``).  Rows and experts reached
are the program's own counts (``expert_rows``, ``experts_hit`` of the
``lo:decode.step`` annotations), scaled to the step runs the device
plane holds: not positions x experts per token, most of whose choices
land on absent experts."""

from lobench import counts, counts_mla, latent_turns

KERNELS = ("gmm", "ragged-dot-none")


def read(record, run):
    turns = latent_turns.read(run)
    if not turns:
        return None
    spans = turns["spans"]
    spent = sum(spans.kernels.get(name, 0.0) for name in KERNELS)
    if spent <= 0 or not spans.steps:
        return None
    scale = len(spans.steps) / turns["read"]
    least, _bound = counts.roofline_seconds(
        counts_mla.experts_flops(run.cp, scale * turns["expert_rows"]),
        counts_mla.experts_bytes(
            run.cp, scale * turns["experts_hit"],
            scale * turns["expert_rows"],
        ), run.peaks,
    )
    return 100.0 * least / spent
