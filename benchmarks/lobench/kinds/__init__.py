"""Traffic kinds: one general generator per kind, driven by the
parameters of a ``traffic/<mix>.json`` file."""
