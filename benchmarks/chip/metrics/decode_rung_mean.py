"""Mean batch rung of the decode windows run in the traced slice (engine
counter ``rungs``, one entry per window)."""


def read(ctx):
    rungs = ctx.stats1["engine"]["rungs"][len(ctx.stats0["engine"]["rungs"]):]
    if not rungs:
        return None
    return sum(rungs) / len(rungs)
