"""Device idle time under the program's `reduce` span (the engine's
combine, run op by op), per batch, ms; None when the label is not among
the ten longest idle labels."""
from bench.spans import idle_ms_per_batch


def read(ctx):
    return idle_ms_per_batch(ctx, "host.reduce")
