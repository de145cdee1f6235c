"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at start-up and the
cache lives there; this module then sets nothing.  Otherwise the cache goes
to one fixed directory inside the checkout (``.jax_cache/``, git-ignored),
so a later run from the same checkout finds what an earlier one compiled.
The path is part of every cache key's context, so it is fixed: never built
from a temporary name, a process id or the time.

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()          # before the first jit compile
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    outside = os.environ.get(ENV_VAR)
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
