"""Decode step: the least bytes the window's decode steps had to move
(the family's ``decode_bytes``; dense: every block's weights, the LM-head
matrix, the live K/V rows of every active slot in every layer, and the row
each slot appends), over their device time, as a share of the chip's HBM
bandwidth."""


def read(ctx):
    sec = ctx.layer_s.get("decode", 0.0) if ctx.trace else 0.0
    if sec <= 0 or not ctx.decode_calls:
        return None
    need = ctx.family.decode_bytes(ctx.dm, ctx.decode_calls, ctx.reports)
    return 100.0 * need / sec / ctx.peaks["hbm_bytes_per_s"]
