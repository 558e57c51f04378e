"""Job engine, leases and artifact store: the window job's wall-clock
that is not its epochs (queue, lease, artifact read, init, publish)."""


def read(record, run):
    job = record.get("job")
    if not job or not job["epoch_times"]:
        return None
    return job["wall_s"] - sum(job["epoch_times"])
