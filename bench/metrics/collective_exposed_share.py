"""Collectives (``core/parallel.py`` TP steps): the share of the window in
which a collective runs on a chip and nothing else does, on the worst
chip."""
from bench import trace as TR


def read(ctx):
    chips = [ctx.devices.get(i, []) for i in range(ctx.cell.chips)]
    if not any(TR.COLLECTIVE.search(n) for ops in chips for n, _, _ in ops):
        return None
    return 100.0 * max(TR.collective_exposed_ns(ops, ctx.lo, ctx.hi)
                       for ops in chips) / (ctx.hi - ctx.lo)
