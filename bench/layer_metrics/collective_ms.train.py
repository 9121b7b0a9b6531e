"""Device time per training step of the workers' collectives, in ms: the
operations of kind all-reduce, all-gather, all-to-all, reduce-scatter or
collective-permute in the trace (asynchronous start and done halves
included), summed on each chip and averaged over the chips.  Nothing is
read where the trace holds none, as in a step on one chip."""
from bench import trace

KINDS = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
         "collective-permute")


def read(ctx):
    w = ctx["window"]
    if "steps" not in w:
        return None
    evs = trace.of_kind(ctx["trace"].all_ops(), KINDS)
    if not evs:
        return None
    return sum(e.dur_ns for e in evs) * 1e-6 / len(ctx["trace"].ops) / w["steps"]
