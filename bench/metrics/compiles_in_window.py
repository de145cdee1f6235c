"""Tick loop: programs compiled or loaded from the persistent cache while
the window ran (JAX's backend-compile events).  Warm-up should leave none."""


def read(ctx):
    return float(ctx.compiles_in_window)
