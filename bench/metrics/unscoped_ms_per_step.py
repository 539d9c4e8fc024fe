"""Device time per step of the train step that no layer's
``jax.named_scope`` claims, in ms (``bench/scopes.py``): it rises where a
layer loses its name."""

from bench import scopes


def read(m):
    return scopes.read_metric(m, "unscoped_ms_per_step")
