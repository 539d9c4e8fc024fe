"""Device time per step that full remat spends recomputing the forward pass
for the backward (operations under ``rematted_computation``), every layer,
in ms (``bench/scopes.py``)."""

from bench import scopes


def read(m):
    return scopes.read_metric(m, "recompute_ms_per_step")
