"""Decode step: the least bytes the window's decode steps had to move,
over their device time, as a share of the chip's HBM bandwidth.

Per step: every block's weights, the LM-head matrix, the live K/V rows of
every active slot in every layer, and the row each slot appends."""


def step_bytes(dm, active: int, live: int) -> int:
    return (dm.layers * dm.layer_weight_bytes + dm.head_bytes
            + (live + active) * dm.layers * dm.kv_row_bytes)


def read(ctx):
    sec = ctx.layer_s.get("decode", 0.0) if ctx.trace else 0.0
    if sec <= 0 or not ctx.decode_calls:
        return None
    need = sum(step_bytes(ctx.dm, a, l) for a, l in ctx.decode_calls)
    return 100.0 * need / sec / ctx.peaks["hbm_bytes_per_s"]
