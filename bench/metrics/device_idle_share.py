"""Share of the traced window in which no operation ran on the device, %."""


def read(ctx):
    if not ctx.device.ops or ctx.device.window_s <= 0.0:
        return None  # no device in the trace (a run on the host alone)
    return 100.0 * (1.0 - ctx.device.busy_s / ctx.device.window_s)
