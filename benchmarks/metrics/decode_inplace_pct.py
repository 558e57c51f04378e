"""Decode engine: the share of the traced turns whose step updated the
pool's KV pages in place, as the engine counts it itself: the
``inplace`` metadata of its ``lo:decode.step`` annotations
(``serve/decode/engine.py`` ``_step_pool``: after the call, whether the
cache that went in reads ``is_deleted()``; cumulative in
``stats()["stepsInPlace"]`` beside ``"steps"``).  One pool steps a turn
in the cell that reports this, so a turn is a step.  A program whose
annotations carry no such key (one that does not donate) reads
nothing."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    steps = spans.named("decode.step") if spans is not None else []
    counted = [int(st["inplace"]) for *_e, st in steps if "inplace" in st]
    if not counted:
        return None
    return 100.0 * sum(counted) / len(counted)
