"""Decode engine: of the chip's wait before a run of the step program,
the mean milliseconds (over the traced steps) that the worker spent in
its ``sync`` phase (reading the step's tokens back:
``np.asarray(col)``): the overlap of that gap with the
``lo:decode.sync`` annotations of ``serve/decode/engine.py``."""

from lobench import hostspans


def read(record, run):
    return hostspans.gap_phase_ms(run, "sync")
