"""Model step (``ops/layers.py`` cached attention, no kernel): the bytes
one step must move (every weight once as float32, and the K and V the
live slots attend over) at the chip's HBM bandwidth, over the mean
device time of a step program in the trace.  Memory-bound."""

from lobench import counts


def read(record, run):
    traced, win = record.get("trace"), record.get("window")
    if not traced or not win or not win["live_samples"]:
        return None
    steps = [
        s for name, runs in traced["modules"].items()
        if name.startswith("jit_step") for s in runs
    ]
    if not steps:
        return None
    live = sum(win["live_samples"]) / len(win["live_samples"])
    nbytes = counts.decoder_weight_bytes(run.cp) \
        + counts.decoder_kv_bytes(run.cp, live * win["mean_keys"])
    least = nbytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(steps) / len(steps))
