"""Device time per training step of the MoE dispatch, in ms: the sort
and gather of the routed pairs and the scatter-add that combines them,
under the program's ``dispatch`` scope, forward, backward and
recomputation (``bench/scopes_dsv2.py``)."""
from bench import scopes_dsv2


def read(ctx):
    return scopes_dsv2.read(ctx, "dispatch")
