"""The check that decides ``correct``.

Once the window has closed and the engine's buffers are freed, a sample of
the requests that the window's last replay finished, drawn from the seed
and always holding the longest one, is scored by the float32 reference of
the configuration's family (``families/<family>_reference.py``): the
prompt and the served tokens go through it, and at each served position
the gap by which the served token's logit lies below the reference's best
is read.  The widest gap over the sample is
compared with the cell's limit (``limits/<cell>.json``), and every request
of the window has to have got all of its tokens.

The control (``run.py --control fp8``) puts the reference in a lower
precision in the program's place: the number compared is then the gap of
the token that the lower-precision pass puts first, at the same positions
of the same sequences, and the same decision has to come out false.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
SAMPLE = 6                   # requests scored, the longest among them


def limits(cell: str) -> dict:
    return json.loads((LIMITS_DIR / f"{cell}.json").read_text())


def sample(reqs: list[dict], seed: int, k: int = SAMPLE) -> list[int]:
    """Indices into ``reqs``: the longest request and k-1 others drawn from
    the seed."""
    size = [len(r["prompt"]) + r["max_new_tokens"] for r in reqs]
    longest = int(np.argmax(size))
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = np.random.default_rng([seed % 2**64, 7])
    return [longest] + sorted(rng.choice(rest, min(k - 1, len(rest)), replace=False).tolist())


def failed_requests(reqs: list[dict], outputs: dict, vocab: int) -> int:
    bad = 0
    for r in reqs:
        toks = outputs.get(r["rid"])
        if toks is None or len(toks) != r["max_new_tokens"] \
                or not all(0 <= t < vocab for t in toks):
            bad += 1
    return bad


def served_gap(fam, seed: int, dm, reqs: list[dict], outputs: dict,
               picks: list[int], controls=()) -> dict:
    """Widest gap over the sampled requests and the tokens scored, and for
    each lower precision in ``controls`` (the family's ``CONTROLS``) that
    pass's widest gap on the same sequences.  A request without all its
    tokens is not scored here: it counts in ``failed_requests``."""
    weights = fam.make_weights(seed, dm)
    out = {"gap": 0.0, "tokens": 0, "controls": dict.fromkeys(controls, 0.0)}
    for i in picks:
        r = reqs[i]
        served = outputs.get(r["rid"])
        if not served or len(served) != r["max_new_tokens"]:
            continue
        g, low = fam.gaps(weights, dm, r["prompt"], served, controls)
        out["gap"] = max(out["gap"], float(g.max()))
        out["tokens"] += len(g)
        for c, x in low.items():
            out["controls"][c] = max(out["controls"][c], float(x.max()))
    return out
