"""Job engine, leases and artifact store: what the window's job spent
making its result durable and visible after the last epoch: the
managed checkpoint (``checkpoint_save``, ``train/neural.py``), the
artifact's parts (``publish``, ``services/executor.py``), the history
rows (``store_history``) and the terminal metadata and journal writes
(``commit``, ``jobs/engine.py``)."""

from lobench import hostspans


def read(record, run):
    return hostspans.span_seconds(
        record, ("checkpoint_save", "publish", "store_history", "commit")
    )
