"""Model FLOP/s utilization of the training step, in %: model FLOPs per
trained token (``counts.train_flops_per_token``) times the traced
window's tokens per second, over the chips' bf16 peak."""
from bench import counts


def read(ctx):
    w = ctx["window"]
    if "tokens" not in w:
        return None
    flops = counts.train_flops_per_token(ctx["config"], w["seq"]) * w["tokens"]
    peak = ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * flops / w["window_s"] / peak
