"""Device: share of the traced window (one whole job, submit to
finished) in which no operation ran on the chip."""


def read(record, run):
    traced = record.get("trace")
    if not traced or not record.get("job") or traced["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
