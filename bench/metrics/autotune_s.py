"""Set-up (``kernels/dispatch.py``): seconds of the ``dispatch.autotune``
spans (one per tuned cell) that end before the timed job's first
``service.batch`` begins: the per-process block sweep paid in set-up."""
from bench import spans as SP


def read(ctx):
    got = SP.recorded()
    if got is None:
        return None
    return SP.before_timed_job(*got, "dispatch.autotune")
