"""Share of the traced slice in which no operation ran on the chip: one
minus the union of the operations' intervals over the slice, averaged
over the chips."""
from benchmarks.chip import xplane


def read(ctx):
    if not ctx.devices or ctx.slice_s <= 0:
        return None
    busy = [xplane.busy_ns(d.ops) / 1e9 for d in ctx.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / ctx.slice_s)
