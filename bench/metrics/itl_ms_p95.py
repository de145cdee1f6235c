"""End to end: 95th percentile of the gaps between consecutive tokens of
one request, host clock.  Each decode tick's interval counts once per
request that got a token in it."""

import numpy as np


def read(ctx):
    gaps = ctx.itl_s
    if not len(gaps):
        return None
    return 1e3 * float(np.percentile(gaps, 95))
