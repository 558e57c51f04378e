"""Decode engine: of the chip's mean wait before a run of the step
program, what none of the worker's four phases (``sync``, ``emit``,
``admit``, ``dispatch``) covers: the gap minus the four overlaps."""

from lobench import hostspans


def read(record, run):
    return hostspans.gap_phase_ms(run, "unnamed")
