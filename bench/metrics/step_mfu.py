"""Decode step and prefill together: model FLOPs of every token the window
prefilled or decoded, over the window's seconds times the chip's bf16 peak.

A decoded token: every block's matmuls, attention over its live context,
the LM head.  A prefilled token at position p: the matmuls and attention
over p + 1 keys; the LM head once per prompt, for its first token."""

from model import token_flops


def read(ctx):
    if ctx.trace is None or ctx.busy_s <= 0:
        return None
    dm = ctx.dm
    flops = 0
    for active, live in ctx.decode_calls:
        flops += active * token_flops(dm, 0, head=True)
        flops += 4 * dm.layers * dm.heads * dm.hd * live
    for c0, c1 in ctx.prefill_spans:
        n = c1 - c0
        flops += n * token_flops(dm, 0, head=False)
        flops += 4 * dm.layers * dm.heads * dm.hd * (c0 + c1 + 1) * n // 2
    flops += ctx.requests_done * 2 * dm.vocab * dm.d
    if not flops:
        return None
    return 100.0 * flops / ctx.trace_window_s / ctx.peaks["bf16_flops_per_s"]
