"""``mfu.train`` of the four-chip training cells, which report
``train4_tokens_per_s``."""
from bench import common


def read(ctx):
    return common.layer_metric("mfu.train").read(ctx)
