"""Set-up: the backend compile durations before the window (a warm cache
makes these loads, not compiles)."""


def read(ctx):
    return ctx.setup["compile_s"]
