"""Prefill programs: their device time per thousand prompt tokens
prefilled (tokens served by the prefix cache are not prefilled)."""


def read(ctx):
    toks = sum(r.prefill_tokens for r in ctx.reports)
    sec = ctx.layer_s.get("prefill", 0.0) if ctx.trace else 0.0
    if not toks or sec <= 0:
        return None
    return 1e3 * sec / toks * 1e3
