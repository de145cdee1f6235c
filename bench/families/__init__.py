"""Model families: everything the harness knows about an architecture.

A configuration file (``configs/<config>.json``) names its family with the
key ``"family"``; family ``x`` is the module ``families/x.py``, with its
float32 reference beside it.  ``run.py``, ``correctness.py``,
``calibrate.py``, ``record_trace.py`` and the metric readers learn about
the architecture from that module alone, so a configuration of a new
family needs only new files.  A family module provides:

    dims(cfg)                      the sizes the rest of the module reads
                                   (hashable: the reference is jitted on it)
    make_weights(seed, dm)         weights from the seed, published layout,
                                   made on the default device in one call
    program_params(seed, dm)       the same weights in the program's layout
    arch_config(cfg, dm)           the program's ``ArchConfig``
    pool_row_bytes(dm)             bytes of one row of the engine's K/V
                                   page pool (K and V, all layers)
    CONTROLS                       the lower-precision passes of ``gaps``
                                   (``fp8``: every matmul input and K/V row;
                                   ``fp8_kv``: the K/V rows alone)
    gaps(weights, dm, prompt, served, controls)
                                   the float32 reference's reading of the
                                   served tokens and of each control's picks
    decode_bytes(dm, calls, reports)
                                   least bytes the window's decode steps move
    walk_bytes(dm, calls, reports) K/V bytes the read kernel must read
    flops(dm, calls, spans, reports)
                                   model FLOPs of the decode steps and the
                                   prefill spans

``calls`` are the window's ``hooks.DecodeCall`` records (active slots,
their summed live rows, and each slot's live rows), ``spans`` the
(first row, end row) of each prefill job a tick, and ``reports`` the
window's ``ServingReport``s, for counters the program keeps.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

import jax

CONFIG_DIR = Path(__file__).resolve().parents[1] / "configs"
PROVIDES = ("dims", "make_weights", "program_params", "arch_config",
            "pool_row_bytes", "CONTROLS", "gaps", "decode_bytes",
            "walk_bytes", "flops")


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def of(cfg: dict):
    """The family module that the configuration names."""
    name = cfg.get("family")
    if not name:
        raise SystemExit(f"bench: configuration {cfg.get('name')!r} names "
                         f"no family (key 'family')")
    try:
        fam = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise SystemExit(f"bench: configuration {cfg.get('name')!r} names "
                         f"family {name!r}, and bench/families/{name}.py "
                         f"does not exist") from None
    missing = [k for k in PROVIDES if not hasattr(fam, k)]
    if missing:
        raise SystemExit(f"bench: family {name!r} does not provide "
                         f"{missing}")
    return fam


def root_key(seed: int) -> jax.Array:
    """A key from any whole number, 64 bits and beyond 32 included."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
