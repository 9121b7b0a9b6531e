"""Share of its roofline that the statistics kernel reaches, in %: the
least time of its work (``counts.fused_stats_bytes`` at the peak HBM
bandwidth, or ``counts.fused_stats_flops`` at the bf16 peak, whichever
is longer: the bytes bound it) over its device time per round in the
trace.  Nothing is read where the trace holds no such kernel."""
from bench import counts, trace

KERNEL = r"fused_stats"


def read(ctx):
    w = ctx["window"]
    if "rounds" not in w:
        return None
    evs = trace.matching(ctx["trace"].all_ops(), KERNEL)
    if not evs:
        return None
    per_round = sum(e.dur_ns for e in evs) * 1e-9 / len(ctx["trace"].ops) / w["rounds"]
    share, _bound = counts.roofline_share(
        counts.fused_stats_flops(w["m"], w["d"]),
        counts.fused_stats_bytes(w["m"], w["d"], w["dtype"]), per_round, ctx["peak"])
    return share
