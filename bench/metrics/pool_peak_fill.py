"""Page pool: the most pool pages that requests held at once (pages mapped
to slots and pending prefills, a shared page once), over the window's
replays, as a share of the pool the engine allocated.  The rest of the
pool is HBM that ``peak_hbm_gb`` pays for and the traffic never uses."""


def read(ctx):
    if not ctx.pool_bytes:
        return None
    return 100.0 * max(r.kv_bytes_live for r in ctx.reports) / ctx.pool_bytes
