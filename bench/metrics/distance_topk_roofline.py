"""Roofline share of the `distance_topk` kernel over the traced window, %."""
from bench.layers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "distance_topk")
