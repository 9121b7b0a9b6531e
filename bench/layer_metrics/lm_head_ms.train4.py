"""``lm_head_ms.train`` of the four-chip training cells, which report
``train4_tokens_per_s``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "lm_head")
