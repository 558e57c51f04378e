"""Trainers: tokens over the summed ``epoch_time`` of the window's job
(each ends after the ``device_get`` that syncs the epoch)."""


def read(record, run):
    job = record.get("job")
    if not job or not job["epoch_times"]:
        return None
    return job["tokens"] / sum(job["epoch_times"])
