"""Tick loop: time the chip sat idle inside the engine's ticks and outside
its blocking reads (the chip waiting on engine host code), per decode
tick.  None where the engine writes no spans."""

from tick_spans import account


def read(ctx):
    acc = account(ctx.trace)
    if acc is None:
        return None
    return 1e3 * acc.idle_host_s / acc.decode_ticks
