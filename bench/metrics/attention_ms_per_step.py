"""Device time per step of the train step's ``attention`` layer (its
``jax.named_scope``), every phase, in ms (``bench/scopes.py``)."""

from bench import scopes


def read(m):
    return scopes.read_metric(m, "attention_ms_per_step")
