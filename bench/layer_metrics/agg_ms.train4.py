"""Device time per training step of the aggregation, in ms: attack
injection, statistics, selection, combine and the workers' collectives
(the program's ``aggregate`` scope; ``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "aggregate")
