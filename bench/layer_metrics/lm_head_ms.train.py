"""Device time per training step of the LM head, in ms: the projection onto
the vocabulary and its cross-entropy, forward and backward (the
program's ``lm_head`` scope; ``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "lm_head")
