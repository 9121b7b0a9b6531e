"""Share of the traced aggregation window in which no operation ran on
the device, in %: 1 - (union of the device's op intervals) / window."""


def read(ctx):
    if "rounds" not in ctx["window"]:
        return None
    s = ctx["trace"]
    return 100.0 * (1.0 - s.busy_s / s.window_s)
