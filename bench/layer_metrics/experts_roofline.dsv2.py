"""Share of its roofline that the held experts' grouped products reach,
in %: the least time of their work over the step's routed pairs
(``counts_dsv2.experts_bytes`` at the peak HBM bandwidth, or
``counts_dsv2.experts_flops`` at the bf16 peak, whichever is longer;
forward and backward, recomputation left out) over the device time per
step under the program's ``experts`` scope."""
from bench import counts, counts_dsv2, scopes_dsv2


def read(ctx):
    w = ctx["window"]
    if "moe_pairs" not in w:
        return None
    ms = scopes_dsv2.read(ctx, "experts")
    if not ms:
        return None
    cfg, pairs = ctx["config"], w["moe_pairs"]
    share, _bound = counts.roofline_share(
        counts_dsv2.experts_flops(cfg, pairs), counts_dsv2.experts_bytes(cfg, pairs),
        ms * 1e-3, ctx["peak"])
    return share
