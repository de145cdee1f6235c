"""Everything the benchmark knows about the serving program's insides.

It builds the program's ``ArchConfig`` and ``ServingConfig`` from a
configuration file, lays the benchmark's weights out as the program wants
them, turns generated requests into the program's ``Request``, and wraps
two engine attributes to see each tick from outside:

* ``_decode``: the batched decode program, called once per tick.  The
  wrapper notes the host clock at entry, the active slots (``slots``) and
  their live rows (``pos`` + 1).  It adds no device sync.
* ``_advance_prefills``: the chunked-prefill lane.  The wrapper notes, per
  pending job (``pending``: ``cursor``, ``S``), the prompt rows it wrote.

A missing attribute stops the run with its name.  No other file of the
benchmark touches the engine.
"""

from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import jax
import numpy as np

import model

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.base import ArchConfig  # noqa: E402
from repro.core.tiered_kv import TieredKVConfig  # noqa: E402
from repro.serve import ServingConfig, ServingEngine  # noqa: E402
from repro.serve.trace import Request  # noqa: E402


def _need(obj, name: str):
    if not hasattr(obj, name):
        raise SystemExit(f"bench/hooks.py: the serving engine has no "
                         f"attribute {name!r}; the benchmark needs it")
    return getattr(obj, name)


def arch_config(cfg: dict, dm: model.Dims) -> ArchConfig:
    return ArchConfig(name=cfg["name"], family="dense", n_layers=dm.layers,
                      d_model=dm.d, n_heads=dm.heads, n_kv_heads=dm.kv_heads,
                      d_ff=dm.f, vocab=dm.vocab, head_dim=dm.hd,
                      qk_norm=dm.qk_norm, rope_theta=dm.rope_theta,
                      tie_embeddings=dm.tied, source=cfg["source"])


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


def _rope_perm(hd: int) -> np.ndarray:
    """The program rotates adjacent pairs (2i, 2i+1); the published code
    rotates (i, i + hd/2).  Reordering the head dim of wq, wk and the qk
    norms maps one onto the other exactly."""
    return np.stack([np.arange(hd // 2), np.arange(hd // 2) + hd // 2],
                    -1).reshape(hd)


def _program_layer(dm: model.Dims):
    perm = _rope_perm(dm.hd)

    def fn(w):
        D, H, Hkv, hd = dm.d, dm.heads, dm.kv_heads, dm.hd
        attn = {"wq": w["wq"].reshape(D, H, hd)[..., perm],
                "wk": w["wk"].reshape(D, Hkv, hd)[..., perm],
                "wv": w["wv"].reshape(D, Hkv, hd),
                "wo": w["wo"].reshape(H, hd, D)}
        if dm.qk_norm:
            attn["q_norm"] = w["q_norm"][perm]
            attn["k_norm"] = w["k_norm"][perm]
        return {"attn_norm": w["attn_norm"], "attn": attn,
                "mlp_norm": w["mlp_norm"],
                "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
    return fn


def program_params(seed: int, dm: model.Dims) -> dict:
    """The benchmark's weights for this seed, in the program's layout, made
    on the device in one call."""
    return model.make_weights(seed, dm, layer_map=_program_layer(dm))


def requests(generated: list[dict]) -> list[Request]:
    return [Request(**r) for r in generated]


class EngineProbe:
    """Host-side record of every tick, from two wrapped engine attributes."""

    def __init__(self, eng: ServingEngine):
        self.eng = eng
        _need(eng, "run")
        self._decode = _need(eng, "_decode")
        self._advance = _need(eng, "_advance_prefills")
        eng._decode = self.decode
        eng._advance_prefills = self.advance
        self.reset()

    def reset(self):
        self.t = []            # host clock at each decode call
        self.active = []       # active slots at each decode call
        self.live = []         # live K/V rows (pos + 1) over active slots
        self.starts = []       # index of each replay's first decode call
        self.spans = []        # (first row, end row) each prefill job wrote

    def replay(self, trace):
        self.starts.append(len(self.t))
        return self.eng.run(trace)

    def decode(self, *args):
        eng = self.eng
        t = time.perf_counter()
        idx = [b for b, s in enumerate(_need(eng, "slots")) if s is not None]
        self.t.append(t)
        self.active.append(len(idx))
        self.live.append(int((np.asarray(_need(eng, "pos"))[idx] + 1).sum()))
        with jax.profiler.TraceAnnotation("bench.decode_dispatch"):
            return self._decode(*args)

    def advance(self, budget):
        before = {s: (_need(j, "cursor"), _need(j, "S"))
                  for s, j in _need(self.eng, "pending").items()}
        with jax.profiler.TraceAnnotation("bench.prefill_chunks"):
            out = self._advance(budget)
        for s, (c0, S) in before.items():
            job = self.eng.pending.get(s)
            c1 = S if job is None else job.cursor
            if c1 > c0:
                self.spans.append((c0, c1))
        return out

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


def pool_bytes(eng: ServingEngine, dm: model.Dims) -> int:
    """Bytes of the engine's K/V page pool, all layers, K and V."""
    pages = _need(eng, "pool_pages")
    return pages * _need(eng, "cfg").tier.page * dm.layers * dm.kv_row_bytes


def free(eng: ServingEngine):
    """Drop the engine's device buffers so the reference has the chip."""
    for name in ("pool_k", "pool_v", "near_k", "near_v", "_shadow_near",
                 "params", "tier"):
        if hasattr(eng, name):
            setattr(eng, name, None)


def set_params(eng: ServingEngine, params):
    """Serve another seed's weights on the same engine (its programs take
    the weights as an argument, so nothing recompiles)."""
    _need(eng, "params")
    eng.params = params
