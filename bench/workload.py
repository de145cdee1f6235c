"""The traffic generator: turns a mix under ``traffic/`` into requests.

A mix is either a data file ``traffic/<mix>.json`` read by the generator
below, or, for a shape the data cannot express, a module
``traffic/<mix>.py`` with a function ``make_requests(seed, vocab)`` that
returns requests in the same form.  A data file's keys:

    requests            requests in one replay of the trace
    arrival_every_ticks arrival gap, in engine ticks
    length_seed         seeds the length schedule; fixed by the mix, so every
                        ``--seed`` gives the same lengths in the same order
                        (and so the same compiled programs)
    shared              null, or {"tokens": n, "askers": k}: k consecutive
                        requests open with the same n-token document
    prompt              the private part of each prompt
    answer              tokens to generate
    ("dist": "uniform" with min/max, or "lognormal" with median/sigma,
     clipped to min/max)
    order               optional; "longest_answer_first" hands the drawn
                        answer lengths out longest first, so that a replay
                        ends on short answers and its slots stay full until
                        its last ticks instead of draining behind a few
                        long ones

Token ids come from ``--seed`` alone.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    py = TRAFFIC_DIR / f"{name}.py"
    if py.is_file():
        spec = importlib.util.spec_from_file_location(f"bench_traffic_{name}",
                                                      py)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return {"generator": mod}
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def make_requests(mix: dict, seed: int, vocab: int) -> list[dict]:
    """Requests as plain dicts: rid, arrival (tick), prompt (int32 array),
    max_new_tokens."""
    if "generator" in mix:
        return mix["generator"].make_requests(seed, vocab)
    n = int(mix["requests"])
    lrng = np.random.default_rng(int(mix["length_seed"]))
    private = _lengths(lrng, mix["prompt"], n)
    answers = _lengths(lrng, mix["answer"], n)
    if mix.get("order") == "longest_answer_first":
        answers = np.sort(answers)[::-1]
    trng = np.random.default_rng(seed % 2**64)
    shared = mix.get("shared")
    doc = None
    out = []
    for i in range(n):
        parts = []
        if shared:
            if i % int(shared["askers"]) == 0:
                doc = trng.integers(0, vocab, int(shared["tokens"]),
                                    dtype=np.int32)
            parts.append(doc)
        parts.append(trng.integers(0, vocab, int(private[i]), dtype=np.int32))
        out.append({"rid": i, "arrival": i * int(mix["arrival_every_ticks"]),
                    "prompt": np.concatenate(parts),
                    "max_new_tokens": int(answers[i])})
    return out
