"""Streaming layer (``engine/streaming.py``): the share of the window the
walk spent waiting on the Γ prefetch (store read, decode, stack and
``device_put``), summed over the counted batches' engine ``io_wait_s``."""


def read(ctx):
    if not ctx.batches:
        return None
    return 100.0 * sum(b["io_wait_s"] for b in ctx.batches) / ctx.window_s
