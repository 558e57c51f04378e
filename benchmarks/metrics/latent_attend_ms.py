"""Kernels (``ops/latent_attention.py``, kernel ``latent_attend``): the
summed device time of the custom calls the trace names
``latent_attend`` over the runs of the step program, in milliseconds a
step (every layer's call).  A program without the kernel (the CPU,
where the plain form runs) reads nothing."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    if spans is None or not spans.steps:
        return None
    spent = spans.kernels.get("latent_attend", 0.0)
    if spent <= 0:
        return None
    return 1e3 * spent / len(spans.steps)
