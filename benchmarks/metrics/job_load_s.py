"""Job engine, leases and artifact store: what the window's job spent
reading before the fit could start: the parent artifact
(``load_artifact``, ``services/executor.py``) and the ``$name``
parameters (``resolve_params``, ``dsl.py``: the dataset read back)."""

from lobench import hostspans


def read(record, run):
    return hostspans.span_seconds(
        record, ("load_artifact", "resolve_params")
    )
