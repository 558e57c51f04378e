"""Model step of a model whose mixer keeps a recurrent state: the
bytes one step must move (the bfloat16 layers and the head once; every
live slot's retention state read once and written once, float32, at the
8,256 products a head whatever the layout pads them to; the step's
activations in and out of the recurrence) at the chip's HBM bandwidth,
over the mean device time of a step program in the trace.  Live slots
a step are the mean over the traced turns' ``lo:decode.step``
annotations.  Memory-bound."""

from lobench import counts_retention, hostspans, retention_turns


def read(record, run):
    traced, turns = record.get("trace"), retention_turns.read(run)
    if not traced or not turns:
        return None
    runs = [
        s for name, rs in traced["modules"].items()
        if name.startswith(hostspans.STEP_PROGRAM) for s in rs
    ]
    if not runs:
        return None
    least = counts_retention.step_bytes(
        run.cp, turns["slot_steps"] / turns["dispatched"]
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(runs) / len(runs))
