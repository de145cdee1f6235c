"""The benchmark's own view of a configuration: its sizes, its weights made
from the seed, and the operations and bytes a step needs.

Nothing here imports the serving program.  Weights are made in the
published (Hugging Face) layout: ``wq`` is (D, H*hd) with the head dim in
rotate-half order, and so on.  ``hooks.py`` maps them onto the program's
layout; ``reference.py`` reads them as they are.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


@dataclass(frozen=True)
class Dims:
    name: str
    vocab: int
    d: int
    f: int
    layers: int
    heads: int
    kv_heads: int
    hd: int
    qk_norm: bool
    tied: bool
    rope_theta: float
    eps: float

    @property
    def mm_params_per_layer(self) -> int:
        """Parameters a token multiplies through in one block."""
        q = self.heads * self.hd
        kv = self.kv_heads * self.hd
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.f

    @property
    def layer_weight_bytes(self) -> int:
        norms = 2 * self.d + (2 * self.hd if self.qk_norm else 0)
        return 2 * (self.mm_params_per_layer + norms)

    @property
    def head_bytes(self) -> int:
        """The LM-head matrix (the embedding when tied), bf16."""
        return 2 * self.vocab * self.d

    @property
    def kv_row_bytes(self) -> int:
        """One token's K and V in one layer, bf16."""
        return 2 * 2 * self.kv_heads * self.hd


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def dims(cfg: dict) -> Dims:
    return Dims(name=cfg["name"], vocab=cfg["vocab_size"],
                d=cfg["hidden_size"], f=cfg["intermediate_size"],
                layers=cfg["num_hidden_layers"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or (cfg["hidden_size"]
                                           // cfg["num_attention_heads"]),
                qk_norm=bool(cfg.get("qk_norm", False)),
                tied=bool(cfg["tie_word_embeddings"]),
                rope_theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


# -- weights -------------------------------------------------------------------

def root_key(seed: int) -> jax.Array:
    """A key from any whole number, 64 bits and beyond 32 included."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _norm_scale(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,))


def layer_weights(key, dm: Dims, dtype=jnp.bfloat16) -> dict:
    """One block's weights in the published layout."""
    ks = jax.random.split(key, 11)
    D, F, q, kv = dm.d, dm.f, dm.heads * dm.hd, dm.kv_heads * dm.hd
    w = {
        "attn_norm": _norm_scale(ks[0], D),
        "wq": jax.random.normal(ks[1], (D, q)) * D ** -0.5,
        "wk": jax.random.normal(ks[2], (D, kv)) * D ** -0.5,
        "wv": jax.random.normal(ks[3], (D, kv)) * D ** -0.5,
        "wo": jax.random.normal(ks[4], (q, D)) * q ** -0.5,
        "mlp_norm": _norm_scale(ks[5], D),
        "w_gate": jax.random.normal(ks[6], (D, F)) * D ** -0.5,
        "w_up": jax.random.normal(ks[7], (D, F)) * D ** -0.5,
        "w_down": jax.random.normal(ks[8], (F, D)) * F ** -0.5,
    }
    if dm.qk_norm:
        w["q_norm"] = _norm_scale(ks[9], dm.hd)
        w["k_norm"] = _norm_scale(ks[10], dm.hd)
    return {k: v.astype(dtype) for k, v in w.items()}


def outer_weights(key, dm: Dims, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and (untied) LM head."""
    ks = jax.random.split(key, 3)
    w = {"embed": jax.random.normal(ks[0], (dm.vocab, dm.d)) * 0.02,
         "final_norm": _norm_scale(ks[1], dm.d)}
    if not dm.tied:
        w["lm_head"] = jax.random.normal(ks[2], (dm.d, dm.vocab)) * dm.d ** -0.5
    return {k: v.astype(dtype) for k, v in w.items()}


def weight_keys(seed: int, dm: Dims):
    k_outer, k_layers = jax.random.split(root_key(seed))
    return k_outer, jax.random.split(k_layers, dm.layers)


def make_weights(seed: int, dm: Dims, layer_map=None) -> dict:
    """All weights in one jitted call on the default device, layer by layer
    (``lax.map``) so that no more than one layer exists in float32 at a
    time.  ``layer_map`` rewrites each layer's dict inside the same call."""
    fn = layer_map or (lambda w: w)

    @jax.jit
    def build(k_outer, k_layers):
        layers = jax.lax.map(lambda k: fn(layer_weights(k, dm)), k_layers)
        return {**outer_weights(k_outer, dm), "layers": layers}

    out = build(*weight_keys(seed, dm))
    jax.block_until_ready(out)
    return out


# -- operations ----------------------------------------------------------------

def token_flops(dm: Dims, context: int, head: bool) -> int:
    """Model FLOPs of one token: every block's matmuls, attention over
    ``context`` keys (scores and values), and the LM head if its logits
    are needed."""
    f = 2 * dm.layers * dm.mm_params_per_layer
    f += 4 * dm.layers * dm.heads * dm.hd * context
    if head:
        f += 2 * dm.vocab * dm.d
    return f
