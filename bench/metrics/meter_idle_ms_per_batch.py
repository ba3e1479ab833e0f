"""Device idle time under the program's `map.meter` span (the engine
metering shuffle bytes), per batch, ms; None when the label is not among
the ten longest idle labels."""
from bench.spans import idle_ms_per_batch


def read(ctx):
    return idle_ms_per_batch(ctx, "host.map.meter")
