"""End to end: seconds from process start to the window's start: weights,
engine, one warm-up replay of the trace (which compiles or loads every
program the window runs)."""


def read(ctx):
    return ctx.setup_s
