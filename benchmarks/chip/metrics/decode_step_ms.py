"""Device time of the decode-window program per decode step: the
program's time in the trace (mean over chips) over the engine's
``decode_steps`` counted in the slice."""

PROGRAM = r"decode_window"


def read(ctx):
    steps = ctx.stats1["decode_steps"] - ctx.stats0["decode_steps"]
    ns = ctx.per_chip_ns(PROGRAM)
    if steps <= 0 or ns == 0:
        return None
    return ns / 1e6 / steps
