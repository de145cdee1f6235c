"""Decode step and prefill together: model FLOPs of every token the window
prefilled or decoded (the family's ``flops``), over the window's seconds
times the chip's bf16 peak.

Dense: a decoded token is every block's matmuls, attention over its live
context, the LM head; a prefilled token at position p, the matmuls and
attention over p + 1 keys; the LM head once per prompt, for its first
token."""


def read(ctx):
    if ctx.trace is None or ctx.busy_s <= 0:
        return None
    flops = ctx.family.flops(ctx.dm, ctx.decode_calls, ctx.prefill_spans,
                             ctx.reports)
    if not flops:
        return None
    return 100.0 * flops / ctx.trace_window_s / ctx.peaks["bf16_flops_per_s"]
