"""Kernels (``ops/ssm.py``, kernel ``ssm_step``): the summed device time
of the custom calls the trace names ``ssm_step`` over the runs of the
step program, in milliseconds a step (every layer's call).  A program
without the kernel (the CPU, where the plain form runs) reads
nothing."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    if spans is None or not spans.steps:
        return None
    spent = spans.kernels.get("ssm_step", 0.0)
    if spent <= 0:
        return None
    return 1e3 * spent / len(spans.steps)
