"""Roofline share of the `cf_refine` kernel over the traced window, %."""
from bench.layers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "cf_refine")
