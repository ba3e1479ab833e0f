"""Share of the traced window the chip needs at its roofline for the
stage-1 work of every batch answered in it, %."""
from bench.layers import map_share


def read(ctx):
    return map_share(ctx, ("stage1",))
