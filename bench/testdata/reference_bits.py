"""Digests of the ``dense`` family's weights and float32 reference logits,
for ``test_bench.py``'s bit-identity test.

    JAX_PLATFORMS=cpu python3 bench/testdata/reference_bits.py \
        CONFIG SEED 'SIZES_JSON'

Prints one JSON object: the SHA-256 (first 16 hex digits) of the bf16
weights in both layouts (as float32 bytes), of the reference's logits at
HIGHEST precision, plain and under each control, for a 100-token prompt
and 30 served tokens drawn from ``default_rng(5)``, and of ``gaps`` on
them.  ``SIZES_JSON`` overrides keys of the configuration.

The process keeps to one CPU core before JAX starts: XLA's CPU backend
splits a matmul's sums by the cores it may use, so the logits' last bits
follow the host's core count unless that count is fixed.
"""

from __future__ import annotations

import os

os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})

import hashlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import families  # noqa: E402
from families import dense, dense_reference  # noqa: E402


def digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree.leaves(tree):
        if x.dtype == jnp.bfloat16:
            x = jnp.asarray(x).astype(jnp.float32)
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()[:16]


def bits(name: str, seed: int, sizes: dict) -> dict:
    dm = dense.dims({**families.load_config(name), **sizes})
    w = dense.make_weights(seed, dm)
    out = {"weights": digest(w),
           "program_weights": digest(dense.program_params(seed, dm))}
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, dm.vocab, 100).astype(np.int32)
    served = rng.integers(0, dm.vocab, 30).tolist()
    tokens, idx, _ = dense_reference._padded(prompt, served)
    for low, key in (("", "logits"), ("fp8", "logits_fp8"),
                     ("fp8_kv", "logits_fp8_kv")):
        out[key] = digest(dense_reference.logits_at(w, tokens, idx, dm,
                                                    low=low))
    gap, lows = dense.gaps(w, dm, prompt, served, dense.CONTROLS)
    out["gaps"] = digest([gap] + [lows[c] for c in dense.CONTROLS])
    return out


if __name__ == "__main__":
    print(json.dumps(bits(sys.argv[1], int(sys.argv[2]),
                          json.loads(sys.argv[3]))))
