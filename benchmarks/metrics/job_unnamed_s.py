"""Job engine, leases and artifact store: the window job's wall-clock
that no leaf span names: the self time of ``job`` and of ``lease``
(what of the ``job`` span no other span covers; ``lease`` holds the
fit, so ``fit_init``, ``epoch`` and ``checkpoint_save`` lie inside it
and it counts as no cover itself) and what the client's clock has
beyond ``queue_wait`` + ``job`` (the ledger record, the client's poll).
Read only where the program names the job's pieces at all (``fit_init``
is there)."""

from lobench import hostspans


def read(record, run):
    job = record.get("job")
    if not job or hostspans.span_seconds(record, ("fit_init",)) is None:
        return None
    outside = job["wall_s"] - (
        hostspans.span_seconds(record, ("queue_wait", "job")) or 0.0
    )
    return outside + hostspans.unnamed_seconds(record)
