"""Kernels (``kernels/contract_measure.py``): the tensor-parallel
contract-and-measure stage's least time per chip over its summed device
time, counted at the published χ split over the model axis."""
from bench import roofline
from bench import trace as TR

KERNEL = r"^contract_measure"


def read(ctx):
    cfg = ctx.cell.config
    n = ctx.plan.micro_batch or (ctx.cell.traffic["samples_per_batch"]
                                 // ctx.plan.p1)
    p2 = ctx.plan.p2
    t_call, _ = roofline.least_time(
        roofline.tp_stage_ops(n, cfg["chi"], cfg["d"], p2),
        roofline.tp_stage_bytes(n, cfg["chi"], cfg["d"], p2,
                                gamma_dtype=cfg["storage_dtype"],
                                gemm_dtype=cfg["gemm_dtype"],
                                env_dtype=cfg["env_dtype"]),
        ctx.peaks)
    durs = [t for ops in ctx.devices.values()
            for t in TR.kernel_events(ops, KERNEL, ctx.lo, ctx.hi)]
    if not durs:
        return None
    return 100.0 * t_call * len(durs) / (sum(durs) / 1e9)
