"""Share of its roofline that the selection-and-combine kernel reaches,
in %: the least time of its work (``counts.select_mean_bytes`` at the
peak HBM bandwidth, or ``counts.select_mean_flops`` at the bf16 peak,
whichever is longer: the bytes bound it) over its device time per round
in the trace.  Nothing is read where the trace holds no such kernel."""
from bench import counts, trace

KERNEL = r"select_mean"


def read(ctx):
    w = ctx["window"]
    if "rounds" not in w:
        return None
    evs = trace.matching(ctx["trace"].all_ops(), KERNEL)
    if not evs:
        return None
    per_round = sum(e.dur_ns for e in evs) * 1e-9 / len(ctx["trace"].ops) / w["rounds"]
    share, _bound = counts.roofline_share(
        counts.select_mean_flops(w["m"], w["d"]),
        counts.select_mean_bytes(w["m"], w["d"], w["dtype"]), per_round, ctx["peak"])
    return share
