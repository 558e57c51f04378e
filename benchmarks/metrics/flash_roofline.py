"""Kernels (``ops/attention.py``): the least time the chip could take for
the attention of every layer and step of the traced job (forward, dq,
dkv; compute-bound at sequence 512, head 64) over the summed device time
of the trace's ``tpu_custom_call`` events, the only custom call in the
fit."""

from lobench import counts


def read(record, run):
    traced, job = record.get("trace"), record.get("job")
    if not traced or not job:
        return None
    spent = traced["ops"].get("custom-call:tpu_custom_call", 0.0)
    if spent <= 0:
        return None
    cp = run.cp
    least, _bound = counts.roofline_seconds(
        counts.flash_train_flops(cp, job["batch_size"], job["seq"]),
        counts.flash_train_bytes(cp, job["batch_size"], job["seq"]),
        run.peaks,
    )
    steps = job["epochs"] * (job["rows"] // job["batch_size"])
    return 100.0 * least * cp["num_layers"] * steps / spent
