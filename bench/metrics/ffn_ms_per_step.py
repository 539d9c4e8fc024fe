"""Device time per step of the train step's ``ffn`` layer (its
``jax.named_scope``), every phase, in ms (``bench/scopes.py``)."""

from bench import scopes


def read(m):
    return scopes.read_metric(m, "ffn_ms_per_step")
