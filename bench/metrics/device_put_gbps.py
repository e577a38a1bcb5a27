"""Streaming layer (``engine/streaming.py``): Γ and Λ bytes handed to
``device_put`` (counter ``put_bytes``) per second of ``engine.device_put``
(counter ``put_s``, until the arrays are ready on the device), over the
counted batches."""


def read(ctx):
    secs = sum(b.get("put_s", 0.0) for b in ctx.batches)
    if secs <= 0:
        return None
    return sum(b["put_bytes"] for b in ctx.batches) / secs / 1e9
