"""Mean `stage2.refine` span over the batches that refined, ms (it runs
stage 1 again inside the refined map)."""
from bench.layers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "stage2_s", only_nonzero=True)
