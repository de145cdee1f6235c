"""Tier maintenance: share of the active slots' attention mass that fell on
near-resident pages, averaged over the window's planning passes."""


def read(ctx):
    xs = [x for r in ctx.reports for x in r.near_hit_mass]
    if not xs:
        return None
    return 100.0 * sum(xs) / len(xs)
