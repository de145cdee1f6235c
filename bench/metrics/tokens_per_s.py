"""End to end: every token emitted in the window over the window's wall
seconds (host clock; the window is whole replays of the trace)."""


def read(ctx):
    return sum(r.tokens for r in ctx.reports) / ctx.window_s
