"""Streaming layer (``engine/streaming.py``): the share of the traced
window in which the chip runs no operation while the walk waits on the Γ
prefetch (inside ``engine.wait_gamma``, placed on the device clock by
``bench/spans.py``), on the idlest chip the cell holds."""
from bench import spans as SP
from bench import trace as TR


def read(ctx):
    chips = [ctx.devices.get(i, []) for i in range(ctx.cell.chips)]
    spans = SP.on_device(ctx)
    if spans is None or not any(chips):
        return None
    waits = SP.named(spans, "engine.wait_gamma", ctx.lo, ctx.hi)
    return 100.0 * max(
        TR.length(TR.subtract(waits, TR.union([(s, e) for _, s, e in ops],
                                              ctx.lo, ctx.hi)))
        for ops in chips) / (ctx.hi - ctx.lo)
