"""Read kernel: live K/V rows the page-table walk read (far pages from the
pool, promoted pages from the near buffer; all layers, K and V), over the
kernel's device time, as a share of the chip's HBM bandwidth.  The
kernel is the decode step's one Pallas call."""

from trace_reduce import KERNEL, op_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    sec = op_seconds(ctx.trace, KERNEL, layer="decode")
    rows = sum(l for _, l in ctx.decode_calls)
    if sec <= 0 or not rows:
        return None
    need = rows * ctx.dm.layers * ctx.dm.kv_row_bytes
    return 100.0 * need / sec / ctx.peaks["hbm_bytes_per_s"]
