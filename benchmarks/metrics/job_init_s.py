"""Trainers: the fit's set-up inside the window's job, from
``NeuralEstimator.fit``'s entry to the first epoch's dispatch
(``fit_init``, ``train/neural.py``): host arrays, state placement, the
program lookup or re-trace, the dataset's upload."""

from lobench import hostspans


def read(record, run):
    return hostspans.span_seconds(record, ("fit_init",))
