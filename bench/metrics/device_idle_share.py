"""Device: the share of the traced window in which no operation runs on
the chip, on the idlest chip the cell holds."""
from bench import trace as TR


def read(ctx):
    chips = [ctx.devices.get(i, []) for i in range(ctx.cell.chips)]
    if not any(chips):
        return None
    span = ctx.hi - ctx.lo
    return 100.0 * max(span - TR.busy_ns(ops, ctx.lo, ctx.hi)
                       for ops in chips) / span
