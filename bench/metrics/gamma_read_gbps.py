"""Γ store (``data/gamma_store.py``): bytes the store read and parsed per
second of its own read time, over the counted batches."""


def read(ctx):
    secs = sum(b["store_io_s"] for b in ctx.batches)
    if secs <= 0:
        return None
    return sum(b["io_bytes"] for b in ctx.batches) / secs / 1e9
