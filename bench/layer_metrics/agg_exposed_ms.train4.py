"""The part of ``agg_ms.train4`` in which no other op runs on the chip,
in ms per training step: aggregation time that nothing hides
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "agg_exposed")
