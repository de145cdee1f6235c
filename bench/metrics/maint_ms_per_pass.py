"""Tier maintenance: device time of the page-mass scoring, planning and
near-buffer refresh programs per planning pass run."""


def read(ctx):
    if ctx.trace is None or not ctx.passes:
        return None
    sec = sum(ctx.layer_s.get(k, 0.0) for k in ("masses", "plan",
                                                 "near_sync"))
    if sec <= 0:
        return None
    return 1e3 * sec / ctx.passes
