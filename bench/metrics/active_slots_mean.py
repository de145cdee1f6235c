"""Tick loop: slots that got a token, averaged over the window's decode
ticks (of ``n_slots``; a slot still prefilling its prompt is not counted).
It tells a backlog that keeps every slot decoding from a replay that ramps
up and drains."""


def read(ctx):
    if not ctx.decode_calls:
        return None
    return sum(c.active for c in ctx.decode_calls) / len(ctx.decode_calls)
