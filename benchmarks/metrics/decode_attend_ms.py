"""Model step (``ops/decode_attention.py``, kernel ``decode_attend``:
the step's rows written into the KV pages in place and the pages read
once, a layer): the summed device time of the custom calls the trace
names ``decode_attend`` over the runs of the step program, in
milliseconds a step.  A program without the kernel (the parent of the
PR that added it; the CPU, where the plain path runs) reads nothing."""

from lobench import hostspans


def read(record, run):
    spans = hostspans.of(run)
    if spans is None or not spans.steps:
        return None
    spent = spans.kernels.get("decode_attend", 0.0)
    if spent <= 0:
        return None
    return 1e3 * spent / len(spans.steps)
