"""Model step of a model whose every layer keeps K/V pages and a
recurrent state side by side: the bytes one step must move (the
bfloat16 layers and the head once; every live slot's float32 SSM state
and convolution tail read once and written once, with the mixer's
step inputs and read-outs; the bfloat16 K and V the live slots attend
over) at the chip's HBM bandwidth, over the mean device time of a step
program in the trace.  Live slots a step are the mean over the traced
turns' ``lo:decode.step`` annotations, keys a slot-step the window's
mean as the clients place the steps.  Memory-bound."""

from lobench import counts_hybrid, hostspans, retention_turns


def read(record, run):
    traced, win = record.get("trace"), record.get("window")
    turns = retention_turns.read(run)
    if not traced or not win or not turns:
        return None
    runs = [
        s for name, rs in traced["modules"].items()
        if name.startswith(hostspans.STEP_PROGRAM) for s in rs
    ]
    if not runs:
        return None
    live = turns["slot_steps"] / turns["dispatched"]
    least = counts_hybrid.step_bytes(
        run.cp, live, live * win["mean_keys"]
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(runs) / len(runs))
