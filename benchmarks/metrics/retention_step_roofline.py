"""Kernels (``ops/retention.py``, kernel ``retention_step``: one step
of the power retention recurrence, every live slot's state of every
key/value head read once, decayed, added to, read out against and
written once): the least time the chip could take for it over the
traced steps (its bytes: live slot-steps x 7 layers x 2 x 34,080,768 B
of state, counted at the 8,256 products of a 128-wide key whatever rows
the program's layout pads them to, and the step's q, k, v, g in and
read-outs out; or its FLOPs, 2 x (8 + 40) x 8,256 x 129 a slot and
layer, whichever is longer: the bytes, by 160 to 1) over the summed
device time of the custom calls the trace names ``retention_step``.
Live slot-steps are the program's own counts in the traced turns'
``lo:decode.step`` annotations, scaled to the step runs the device
plane holds.  Where the plain form runs (the CPU; a program without the
kernel) there is no such custom call and nothing to read."""

from lobench import counts, counts_retention, retention_turns


def read(record, run):
    turns = retention_turns.read(run)
    if not turns:
        return None
    spans = turns["spans"]
    spent = spans.kernels.get("retention_step", 0.0)
    if spent <= 0 or not spans.steps:
        return None
    live = turns["slot_steps"] * len(spans.steps) / turns["dispatched"]
    least, _bound = counts.roofline_seconds(
        counts_retention.retention_flops(run.cp, live),
        counts_retention.retention_bytes(run.cp, live), run.peaks,
    )
    return 100.0 * least / spent
