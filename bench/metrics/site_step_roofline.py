"""Kernels (``kernels/site_step.py``): the fused site step's least time
(operations over peak or bytes over HBM bandwidth, whichever is larger,
counted at the published χ and the stated dtypes) over its summed device
time in the window.  The bound that applies is compute: see PERF.md."""
from bench import roofline
from bench import trace as TR

KERNEL = r"^site_step_linear"


def read(ctx):
    cfg = ctx.cell.config
    n = ctx.plan.micro_batch or ctx.cell.traffic["samples_per_batch"]
    t_call, _ = roofline.least_time(
        roofline.site_step_ops(n, cfg["chi"], cfg["d"]),
        roofline.site_step_bytes(n, cfg["chi"], cfg["d"],
                                 gamma_dtype=cfg["storage_dtype"],
                                 env_dtype=cfg["env_dtype"]),
        ctx.peaks)
    durs = [t for ops in ctx.devices.values()
            for t in TR.kernel_events(ops, KERNEL, ctx.lo, ctx.hi)]
    if not durs:
        return None
    return 100.0 * t_call * len(durs) / (sum(durs) / 1e9)
