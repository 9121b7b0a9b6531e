"""Device time per training step of the backward pass, in ms: the ops traced
under the program's ``loss`` scope and autodiff's ``transpose``,
recomputation and the LM head included (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "backward")
