"""Prefix cache: share of prompt tokens whose K/V came from cached pages
(``prefix_hit_tokens / prefill_tokens_full`` over the window's replays)."""


def read(ctx):
    full = sum(r.prefill_tokens_full for r in ctx.reports)
    if not full:
        return None
    return 100.0 * sum(r.prefix_hit_tokens for r in ctx.reports) / full
