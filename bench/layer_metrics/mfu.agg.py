"""The aggregation round's share of the chip's roofline, in %: the least
work of one round (read G once, write the float32 aggregate once;
``counts.agg_round_bytes``, its operations negligible beside it) at the
peak HBM bandwidth, over the traced window's time per round.  The same
work whatever implements it, so it bounds a gain after a change that
fuses or removes a kernel."""
from bench import counts


def read(ctx):
    w = ctx["window"]
    if "rounds" not in w:
        return None
    least_s = counts.agg_round_bytes(w["m"], w["d"], w["dtype"]) / \
        ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (w["window_s"] / w["rounds"])
