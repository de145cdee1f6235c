"""Tick loop: time the chip sat idle inside the engine's blocking reads of
device values (``serve.wait.*`` spans: the round trip each read exposes),
per decode tick.  None where the engine writes no spans."""

from tick_spans import account


def read(ctx):
    acc = account(ctx.trace)
    if acc is None:
        return None
    return 1e3 * acc.idle_wait_s / acc.decode_ticks
