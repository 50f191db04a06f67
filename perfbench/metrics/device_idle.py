"""Share of the traced window in which no op ran on the chip, averaged over
the cell's chips."""
from perfbench import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    win = tracing.window_seconds(ctx.trace)
    busy = tracing.busy_seconds(ctx.trace)
    return 100.0 * (1.0 - sum(busy) / (len(busy) * win))
