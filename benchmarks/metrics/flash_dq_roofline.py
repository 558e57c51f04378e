"""Kernels (``ops/attention.py``, ``flash_dq``: dP and dQ; the scores
it recomputes never count): the least time the chip could take for the
kernel over the traced job (its two T x T x head matmuls a head, or its
tensors moved once, whichever is longer) over the summed device time of
the custom calls the trace names ``flash_dq``."""

from lobench import hostspans


def read(record, run):
    return hostspans.flash_kernel_roofline(record, run, "flash_dq")
