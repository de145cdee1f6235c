"""Tick loop: host time of the engine's ticks (``serve.tick`` spans in the
window, less the blocking reads inside them, ``serve.wait.*``) per decode
tick (``serve.decode`` span).  None where the engine writes no spans."""

from tick_spans import account


def read(ctx):
    acc = account(ctx.trace)
    if acc is None:
        return None
    return 1e3 * (acc.tick_s - acc.tick_wait_s) / acc.decode_ticks
