"""Device time per training step of DeepSeek-V2's latent attention, in
ms: the ops under the program's ``mla`` scope, forward, backward and
recomputation (``bench/scopes_dsv2.py``)."""
from bench import scopes_dsv2


def read(ctx):
    return scopes_dsv2.read(ctx, "mla")
