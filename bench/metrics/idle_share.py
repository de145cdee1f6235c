"""Tick loop: share of the traced window in which no op ran on the chip."""


def read(ctx):
    if ctx.trace is None or ctx.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.trace_window_s)
