"""Kernels (``ops/ssm.py``, kernel ``ssm_step``: one position of the
Mamba-2 recurrence, every live slot's state of every head read once,
decayed, added to, read out against ``C`` and written once): the least
time the chip could take for it over the traced steps (its bytes: live
slot-steps x 6 layers x (2 x 4,194,304 B of float32 state, and the
step's x, B, C and step sizes in and read-outs out); or its FLOPs, 4 a
state value, whichever is longer: the bytes, by 480 to 1) over the
summed device time of the custom calls the trace names ``ssm_step``.
Live slot-steps are the program's own counts in the traced turns'
``lo:decode.step`` annotations, scaled to the step runs the device
plane holds.  Where the plain form runs (the CPU; a program without the
kernel) there is no such custom call and nothing to read."""

from lobench import counts, counts_hybrid, retention_turns


def read(record, run):
    turns = retention_turns.read(run)
    if not turns:
        return None
    spans = turns["spans"]
    spent = spans.kernels.get("ssm_step", 0.0)
    if spent <= 0 or not spans.steps:
        return None
    live = turns["slot_steps"] * len(spans.steps) / turns["dispatched"]
    least, _bound = counts.roofline_seconds(
        counts_hybrid.ssm_flops(run.cp, live),
        counts_hybrid.ssm_bytes(run.cp, live), run.peaks,
    )
    return 100.0 * least / spent
