"""Roofline share of the `refine_distances` kernel over the traced window, %."""
from bench.layers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "refine_distances")
