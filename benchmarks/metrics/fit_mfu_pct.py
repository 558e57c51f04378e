"""Trainers, the whole step: FLOPs a token from shapes (forward and
backward, nothing recomputed, attention included) times tokens a second
of the epochs, over the chip's bf16 peak."""

from lobench import counts


def read(record, run):
    job = record.get("job")
    if not job or not job["epoch_times"]:
        return None
    rate = job["tokens"] / sum(job["epoch_times"])
    flops = counts.encoder_train_flops_per_token(run.cp, job["seq"])
    return 100.0 * flops * rate / run.peaks["flops_bf16"]
