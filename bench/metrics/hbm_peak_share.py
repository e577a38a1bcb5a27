"""Device: the fullest chip's peak bytes in use over its byte limit, as the
runtime reports them after the window."""


def read(ctx):
    shares = [m["peak_bytes_in_use"] / m["bytes_limit"] for m in ctx.memory
              if m.get("bytes_limit") and "peak_bytes_in_use" in m]
    return 100.0 * max(shares) if shares else None
