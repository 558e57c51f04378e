"""Kernels (``ops/latent_attention.py``, kernel ``latent_attend``: the
absorbed attention of a decode step, one read-only pass a layer over
the latent pages, whose first 512 columns are also the values): the
least time the chip could take for it over the traced steps (its FLOPs,
64 heads x (576 + 512) x 2 a key, or its bytes: each attended latent
row read ONCE, 1,152 B a key and layer, and the queries in and mixes
out; whichever is longer) over the summed device time of the custom
calls the trace names ``latent_attend``.  Keys and query tokens are the
program's own counts in the traced turns' ``lo:decode.step``
annotations, scaled to the step runs the device plane holds.  Where
the plain form runs (the CPU; a program without the kernel) there is no
such custom call and nothing to read."""

from lobench import counts, counts_mla, latent_turns


def read(record, run):
    turns = latent_turns.read(run)
    if not turns:
        return None
    spans = turns["spans"]
    spent = spans.kernels.get("latent_attend", 0.0)
    if spent <= 0 or not spans.steps:
        return None
    scale = len(spans.steps) / turns["dispatched"]
    least, _bound = counts.roofline_seconds(
        counts_mla.attend_flops(run.cp, scale * turns["keys"]),
        counts_mla.attend_bytes(
            run.cp, scale * turns["keys"], scale * turns["tokens"]
        ), run.peaks,
    )
    return 100.0 * least / spent
