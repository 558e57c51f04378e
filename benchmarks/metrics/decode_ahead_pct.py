"""Decode engine: the share of the traced turns whose step was enqueued
while the pool's step before it was still unread, so that the host's
turn for that one (reading its tokens, handing them to their streams,
the next admission) ran beside the chip: the ``ahead`` metadata of the
engine's ``lo:decode.step`` annotations (``serve/decode/engine.py``
``_dispatch``; cumulative in ``stats()["stepsAhead"]`` beside
``"steps"``).  A one-token pool is ahead on every step but the first
after it stood drained; a block pool, whose next input is decided from
its last result, never is.  One pool steps a turn in the cells that
report this, so a turn is a step; a turn that dispatched no step (it
only read the last one back) is left out.  A program whose annotations
carry no such key reads nothing."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    steps = spans.named("decode.step") if spans is not None else []
    counted = [
        int(st["ahead"]) for *_e, st in steps
        if "ahead" in st and int(st.get("slots", 1))
    ]
    if not counted:
        return None
    return 100.0 * sum(counted) / len(counted)
