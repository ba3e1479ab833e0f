"""Mean `stage1` span per batch, ms: the immediate answer from the aggregates."""
from bench.layers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "stage1_s")
