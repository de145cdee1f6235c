"""Tick loop: blocking reads of device values the engine made
(``ServingReport.host_syncs``) per decode step, over the window's replays.
None where the engine does not count them."""


def read(ctx):
    syncs = [getattr(r, "host_syncs", None) for r in ctx.reports]
    steps = sum(r.steps for r in ctx.reports)
    if None in syncs or not steps:
        return None
    return sum(syncs) / steps
