"""Mean time a request waited in the batcher (`batcher.wait` spans), ms."""
from bench.layers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "waits_s")
