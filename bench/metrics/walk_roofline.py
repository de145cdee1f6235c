"""Read kernel: the K/V bytes the page-table walk had to read (the family's
``walk_bytes``; dense: live K/V rows of every active slot, far pages from
the pool and promoted pages from the near buffer, all layers, K and V),
over the kernel's device time, as a share of the chip's HBM bandwidth.
The kernel is the decode step's one Pallas call."""

from trace_reduce import KERNEL, op_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    sec = op_seconds(ctx.trace, KERNEL, layer="decode")
    if sec <= 0 or not sum(c.live for c in ctx.decode_calls):
        return None
    need = ctx.family.walk_bytes(ctx.dm, ctx.decode_calls, ctx.reports)
    return 100.0 * need / sec / ctx.peaks["hbm_bytes_per_s"]
