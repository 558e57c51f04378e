"""Decode engine: of the chip's wait before a run of the step program,
the mean milliseconds (over the traced steps) that the worker spent in
its ``admit`` phase (the loop top: pending streams, admission, the
abort sweep): the overlap of that gap with the ``lo:decode.admit``
annotations of ``serve/decode/engine.py``."""

from lobench import hostspans


def read(record, run):
    return hostspans.gap_phase_ms(run, "admit")
