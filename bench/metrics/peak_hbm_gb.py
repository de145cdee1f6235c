"""End to end: peak device memory in use on the fullest chip over the
run up to the window's close (``memory_stats()["peak_bytes_in_use"]``)."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
