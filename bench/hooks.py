"""Everything the benchmark knows about the serving program's insides,
apart from the architecture (``families/``).

It builds the program's ``ServingConfig`` from a configuration file, turns
generated requests into the program's ``Request``, and wraps two engine
attributes to see each tick from outside:

* ``_decode``: the batched decode program, called once per tick.  The
  wrapper notes the host clock at entry, the active slots (``slots``) and
  each one's live rows (``pos`` + 1, from the engine's host mirror).  It
  adds no device sync.
* ``_advance_prefills``: the chunked-prefill lane.  The wrapper notes, per
  pending job (``pending``: ``cursor``, ``S``), the prompt rows it wrote.

A missing attribute stops the run with its name.  No other file of the
benchmark touches the engine except through ``need`` and the program
classes this module exports.
"""

from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path
from typing import NamedTuple

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.base import ArchConfig  # noqa: E402,F401
from repro.core.tiered_kv import TieredKVConfig  # noqa: E402
from repro.serve import ServingConfig, ServingEngine  # noqa: E402
from repro.serve.trace import Request  # noqa: E402


def need(obj, name: str):
    if not hasattr(obj, name):
        raise SystemExit(f"bench/hooks.py: the serving engine has no "
                         f"attribute {name!r}; the benchmark needs it")
    return getattr(obj, name)


def serving_config(engine: dict) -> ServingConfig:
    """Engine settings by name; a setting the program no longer has is
    named on stderr and left out."""
    tier_keys = {f.name for f in dataclasses.fields(TieredKVConfig)}
    serve_keys = {f.name for f in dataclasses.fields(ServingConfig)} - {"tier"}
    tier, serve = {}, {}
    for k, v in engine.items():
        if k in tier_keys:
            tier[k] = v
        elif k in serve_keys:
            serve[k] = v
        else:
            print(f"bench: engine setting {k!r} is not in the program; "
                  f"left out", file=sys.stderr)
    return ServingConfig(tier=TieredKVConfig(**tier), **serve)


def requests(generated: list[dict]) -> list[Request]:
    return [Request(**r) for r in generated]


class DecodeCall(NamedTuple):
    """One decode call: active slots, their live K/V rows summed, and each
    active slot's live rows (in slot order)."""
    active: int
    live: int
    rows: tuple


class EngineProbe:
    """Host-side record of every tick, from two wrapped engine attributes."""

    def __init__(self, eng: ServingEngine):
        self.eng = eng
        need(eng, "run")
        self._decode = need(eng, "_decode")
        self._advance = need(eng, "_advance_prefills")
        eng._decode = self.decode
        eng._advance_prefills = self.advance
        self.reset()

    def reset(self):
        self.t = []            # host clock at each decode call
        self.active = []       # active slots at each decode call
        self.live = []         # live K/V rows (pos + 1) over active slots
        self.rows = []         # the same, each active slot's own
        self.starts = []       # index of each replay's first decode call
        self.spans = []        # (first row, end row) each prefill job wrote

    def replay(self, trace):
        self.starts.append(len(self.t))
        return self.eng.run(trace)

    def decode(self, *args):
        eng = self.eng
        t = time.perf_counter()
        idx = [b for b, s in enumerate(need(eng, "slots")) if s is not None]
        self.t.append(t)
        self.active.append(len(idx))
        rows = np.asarray(need(eng, "pos"))[idx] + 1
        self.live.append(int(rows.sum()))
        self.rows.append(tuple(rows.tolist()))
        with jax.profiler.TraceAnnotation("bench.decode_dispatch"):
            return self._decode(*args)

    def advance(self, budget):
        before = {s: (need(j, "cursor"), need(j, "S"))
                  for s, j in need(self.eng, "pending").items()}
        with jax.profiler.TraceAnnotation("bench.prefill_chunks"):
            out = self._advance(budget)
        for s, (c0, S) in before.items():
            job = self.eng.pending.get(s)
            c1 = S if job is None else job.cursor
            if c1 > c0:
                self.spans.append((c0, c1))
        return out

    def calls(self) -> list[DecodeCall]:
        return [DecodeCall(*c) for c in zip(self.active, self.live,
                                            self.rows)]

    def itl_samples(self) -> np.ndarray:
        """Gaps between consecutive tokens of one request: each decode
        call's interval since the previous call of its replay, once per
        active slot."""
        t = np.asarray(self.t)
        a = np.asarray(self.active)
        gaps, ends = [], self.starts[1:] + [len(t)]
        for s, e in zip(self.starts, ends):
            d = np.diff(t[s:e])
            gaps.append(np.repeat(d, a[s + 1:e]))
        return np.concatenate(gaps) if gaps else np.zeros(0)


def pool_bytes(eng: ServingEngine, row_bytes: int) -> int:
    """Bytes of the engine's K/V page pool, at ``row_bytes`` a row (the
    family's ``pool_row_bytes``)."""
    return need(eng, "pool_pages") * need(eng, "cfg").tier.page * row_bytes


def free(eng: ServingEngine):
    """Drop the engine's device buffers so the reference has the chip."""
    for name in ("pool_k", "pool_v", "near_k", "near_v", "_shadow_near",
                 "params", "tier"):
        if hasattr(eng, name):
            setattr(eng, name, None)


def set_params(eng: ServingEngine, params):
    """Serve another seed's weights on the same engine (its programs take
    the weights as an argument, so nothing recompiles)."""
    need(eng, "params")
    eng.params = params
