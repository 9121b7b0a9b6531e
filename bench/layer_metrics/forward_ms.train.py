"""Device time per training step of the forward pass, in ms: the ops traced
under the program's ``loss`` scope outside autodiff's ``transpose``, the
LM head included (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "forward")
