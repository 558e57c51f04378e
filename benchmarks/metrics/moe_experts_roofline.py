"""Kernels (``ops/moe.py`` ``grouped_matmul``: the three grouped
matmuls a layer over the rows sorted by expert): the least time the
chip could take for them over the traced steps (their FLOPs, or the
bytes of the experts reached and of the rows, whichever is longer) over
the summed device time of the custom calls the trace names ``gmm``
(jax's megablox kernel, the TPU path) or ``ragged-dot-none`` (what XLA
makes of ``jax.lax.ragged_dot``).  Experts reached and positions are the
program's own counts in the traced turns' ``lo:decode.step``
annotations, scaled to the step runs the device plane holds."""

from lobench import counts, counts_moe, hostspans

KERNELS = ("gmm", "ragged-dot-none")


def read(record, run):
    spans = hostspans.of(run)
    if spans is None:
        return None
    spent = sum(spans.kernels.get(name, 0.0) for name in KERNELS)
    turns = [st for *_e, st in spans.named("decode.step")
             if int(st.get("positions", 0))]
    if spent <= 0 or not turns or not spans.steps:
        return None
    scale = len(spans.steps) / len(turns)
    # a free slot's rows are routed and multiplied like any other
    positions = scale * sum(
        int(st["slots"]) for st in turns) * run.cp["block_length"]
    hit = scale * sum(int(st["experts_hit"]) for st in turns)
    least, _bound = counts.roofline_seconds(
        counts_moe.experts_flops(run.cp, positions),
        counts_moe.experts_bytes(run.cp, hit, positions), run.peaks,
    )
    return 100.0 * least / spent
