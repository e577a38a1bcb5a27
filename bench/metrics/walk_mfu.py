"""Walk (``core/sampler.py``, ``core/parallel.py``): the rate in
site-samples per second times the operations one site-sample needs at the
published χ, over the peak of the chips the cell holds."""
from bench import roofline


def read(ctx):
    cfg = ctx.cell.config
    ops = roofline.site_sample_ops(cfg["chi"], cfg["d"])
    return 100.0 * ctx.rate * ops / (ctx.cell.chips * ctx.peaks["bf16_flops"])
