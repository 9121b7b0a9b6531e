"""Device time per training step of the optimizer, in ms: the update and
the gradient norm (the program's ``optimizer`` scope;
``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "optimizer")
