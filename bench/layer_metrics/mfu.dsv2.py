"""Model FLOP/s utilization of DeepSeek-V2's training step, in %: model
FLOPs per trained token (``counts_dsv2.train_flops_per_token``, the held
experts' share from the pairs they computed) times the traced window's
tokens per second, over the chips' bf16 peak."""
from bench import counts_dsv2


def read(ctx):
    w = ctx["window"]
    if "moe_pairs" not in w or not w.get("tokens"):
        return None
    per_token = w["moe_pairs"] * w["steps"] / w["tokens"]
    flops = counts_dsv2.train_flops_per_token(ctx["config"], w["seq"], per_token) * w["tokens"]
    peak = ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * flops / w["window_s"] / peak
