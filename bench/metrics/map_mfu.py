"""Share of the traced window the chip needs at its roofline for the whole
map (stage 1 and, where granted, stage 2) of every batch answered in it, %."""
from bench.layers import map_share


def read(ctx):
    return map_share(ctx, ("stage1", "stage2"))
