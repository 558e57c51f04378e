"""Decode engine: of the chip's wait before a run of the step program,
the mean milliseconds (over the traced steps) that the worker spent in
its ``dispatch`` phase (the step's host arrays, ``_params_for``, the
``step(...)`` call): the overlap of that gap with the
``lo:decode.dispatch`` annotations of ``serve/decode/engine.py``."""

from lobench import hostspans


def read(record, run):
    return hostspans.gap_phase_ms(run, "dispatch")
