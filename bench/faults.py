"""Faults planted under the timed path, to see ``correct`` come out false.

    python3 bench/run.py ... --fault drop_appends     # on the chip
    test_bench.py                                      # on the CPU

Each takes the engine after ``hooks.EngineProbe`` has wrapped it and wraps
its decode program once more.
"""


def drop_appends(eng):
    """The decode step leaves the K/V pool as it was (every append goes to
    the dropped sentinel page)."""
    inner = eng._decode

    def decode(params, cache, tokens, meta):
        pool_pages = cache["pool_k"].shape[1]
        meta = {**meta, "append_pid": meta["append_pid"] * 0 + pool_pages}
        return inner(params, cache, tokens, meta)
    eng._decode = decode


def alter_tokens(eng):
    """Every decoded token comes out one id higher."""
    import jax.numpy as jnp
    inner = eng._decode

    def decode(*args):
        logits, cache, aux = inner(*args)
        return jnp.roll(logits, 1, axis=-1), cache, aux
    eng._decode = decode


FAULTS = {f.__name__: f for f in (drop_appends, alter_tokens)}
