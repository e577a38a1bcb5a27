"""Streaming layer (``engine/streaming.py``): the engine's summed
``engine.fetch`` time (read, decode, stack, pad, ``device_put``; counter
``fetch_s``) over the counted batches, as a share of the window.  Near
100 % the one prefetch thread sets the pace."""


def read(ctx):
    fetch = [b.get("fetch_s") for b in ctx.batches]
    if not fetch or None in fetch:
        return None
    return 100.0 * sum(fetch) / ctx.window_s
