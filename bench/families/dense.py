"""The ``dense`` family: Qwen3 / Llama-style decoders, every layer a GQA
block with a SwiGLU MLP (``families/__init__.py`` says what a family
provides).

Weights are made in the published (Hugging Face) layout: ``wq`` is
(D, H*hd) with the head dim in rotate-half order, and so on.
``program_params`` maps them onto the program's layout;
``dense_reference.py`` reads them as they are.  The serving program is
reached through ``hooks.py`` alone.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import hooks
from families import root_key
from families.dense_reference import CONTROLS, gaps  # noqa: F401

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


# -- the program's view -------------------------------------------------------

def arch_config(cfg: dict, dm: Dims) -> hooks.ArchConfig:
    return hooks.ArchConfig(
        name=cfg["name"], family="dense", n_layers=dm.layers, d_model=dm.d,
        n_heads=dm.heads, n_kv_heads=dm.kv_heads, d_ff=dm.f, vocab=dm.vocab,
        head_dim=dm.hd, qk_norm=dm.qk_norm, rope_theta=dm.rope_theta,
        tie_embeddings=dm.tied, source=cfg["source"])


def _rope_perm(hd: int) -> np.ndarray:
    """The program rotates adjacent pairs (2i, 2i+1); the published code
    rotates (i, i + hd/2).  Reordering the head dim of wq, wk and the qk
    norms maps one onto the other exactly."""
    return np.stack([np.arange(hd // 2), np.arange(hd // 2) + hd // 2],
                    -1).reshape(hd)


def _program_layer(dm: Dims):
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


def program_params(seed: int, dm: Dims) -> dict:
    """The benchmark's weights for this seed, in the program's layout, made
    on the device in one call."""
    return make_weights(seed, dm, layer_map=_program_layer(dm))


def pool_row_bytes(dm: Dims) -> int:
    """Bytes of one row of the engine's K/V page pool: K and V, all
    layers."""
    return dm.layers * dm.kv_row_bytes


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


def step_bytes(dm: Dims, active: int, live: int) -> int:
    """Least bytes of one decode step: every block's weights, the LM-head
    matrix, the live K/V rows of every active slot in every layer, and the
    row each slot appends."""
    return (dm.layers * dm.layer_weight_bytes + dm.head_bytes
            + (live + active) * dm.layers * dm.kv_row_bytes)


def decode_bytes(dm: Dims, calls, reports) -> int:
    return sum(step_bytes(dm, c.active, c.live) for c in calls)


def walk_bytes(dm: Dims, calls, reports) -> int:
    """The live K/V rows of every active slot, all layers, K and V."""
    return sum(c.live for c in calls) * dm.layers * dm.kv_row_bytes


def flops(dm: Dims, calls, spans, reports) -> int:
    """A decoded token: every block's matmuls, attention over its live
    context, the LM head.  A prefilled token at position p: the matmuls and
    attention over p + 1 keys; the LM head once per request, for its first
    token."""
    f = 0
    for c in calls:
        f += c.active * token_flops(dm, 0, head=True)
        f += 4 * dm.layers * dm.heads * dm.hd * c.live
    for c0, c1 in spans:
        n = c1 - c0
        f += n * token_flops(dm, 0, head=False)
        f += 4 * dm.layers * dm.heads * dm.hd * (c0 + c1 + 1) * n // 2
    f += sum(len(r.outputs) for r in reports) * 2 * dm.vocab * dm.d
    return f
