"""The ``dense`` family's reference: a plain float32 forward pass of a
Qwen3 / Llama-style decoder, for the check that decides ``correct``.

It follows the published blocks (Hugging Face ``Qwen3ForCausalLM`` and
``LlamaForCausalLM``): RMSNorm with a weight, q/k/v projections, a
per-head RMSNorm of q and k where the configuration has it (Qwen3), RoPE
in rotate-half form, causal grouped-query attention, an output
projection, a SwiGLU MLP, a final RMSNorm and the LM head (the embedding
when tied).  Every matmul runs at ``Precision.HIGHEST``.  No cache, no
batching, no kernel, nothing from the serving program.

``low="fp8"`` is the control: the same pass with every matmul input (the
activations per token, the weights per output column) and every K and V
row rounded to float8 e4m3 under an absmax scale, the step below the
configuration's bfloat16.  ``low="fp8_kv"`` rounds the K and V rows
alone: the cache stored in float8, the shortcut nearest to this system.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:
    from families.dense import Dims

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
CONTROLS = ("fp8", "fp8_kv")     # lower-precision passes, see the docstring
Q_BLOCK = 512          # queries per attention block (bounds the score matrix)
PAD = 1024             # sequence lengths pad up to a multiple of this, and
HEAD_PAD = 768         # scored positions to one of this: a few shapes only,
                       # so a run loads the reference's programs, not compiles


def _fq(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, w, low):
    """a (..., K) @ w (K, N) in float32."""
    if low == "fp8":
        a, w = _fq(a, -1), _fq(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, h, hd), rotate-half RoPE as in the published code."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, dm: Dims):
    """Causal GQA.  q (S, H, hd), k/v (S, Hkv, hd) -> (S, H*hd)."""
    S = q.shape[0]
    g = dm.heads // dm.kv_heads
    qg = q.reshape(S // Q_BLOCK, Q_BLOCK, dm.kv_heads, g, dm.hd)
    kpos = jnp.arange(S)

    def block(args):
        qb, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HIGHEST)
        s = s * dm.hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (qg, jnp.arange(S // Q_BLOCK)))
    return out.reshape(S, dm.heads * dm.hd)


def _block(x, w, pos, dm: Dims, low: str):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    S = x.shape[0]
    h = _rms(x, w["attn_norm"], dm.eps)
    q = _mm(h, w["wq"], low).reshape(S, dm.heads, dm.hd)
    k = _mm(h, w["wk"], low).reshape(S, dm.kv_heads, dm.hd)
    v = _mm(h, w["wv"], low).reshape(S, dm.kv_heads, dm.hd)
    if dm.qk_norm:
        q = _rms(q, w["q_norm"], dm.eps)
        k = _rms(k, w["k_norm"], dm.eps)
    q, k = _rope(q, pos, dm.rope_theta), _rope(k, pos, dm.rope_theta)
    if low:
        k, v = _fq(k, -1), _fq(v, -1)
    x = x + _mm(_attention(q, k, v, dm), w["wo"], low)
    h = _rms(x, w["mlp_norm"], dm.eps)
    act = jax.nn.silu(_mm(h, w["w_gate"], low)) * _mm(h, w["w_up"], low)
    return x + _mm(act, w["w_down"], low)


@functools.partial(jax.jit, static_argnames=("dm", "low"))
def logits_at(weights, tokens, idx, dm: Dims, low: str = ""):
    """Logits (len(idx), V) at positions ``idx`` of the sequence
    ``tokens`` (both padded by the caller)."""
    x = weights["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])

    def body(x, w):
        return _block(x, w, pos, dm, low), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    h = _rms(x[idx], weights["final_norm"].astype(jnp.float32), dm.eps)
    head = weights["embed"].T if dm.tied else weights["lm_head"]
    return _mm(h, head.astype(jnp.float32), low)


def _padded(prompt: np.ndarray, served: list[int]):
    """The sequence that scores ``served``: prompt + served[:-1], and the
    positions whose logits predict each served token."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = len(served)
    S = -(-len(seq) // PAD) * PAD
    tokens = np.zeros(S, np.int32)
    tokens[:len(seq)] = seq
    m = -(-n // HEAD_PAD) * HEAD_PAD
    idx = np.full(m, len(prompt) - 1, np.int32)
    idx[:n] = len(prompt) - 1 + np.arange(n)
    return jnp.asarray(tokens), jnp.asarray(idx), n


def gaps(weights, dm: Dims, prompt, served, controls=()):
    """Per served token, how far its reference logit lies below the
    reference's best logit at that position (0 where it is the best).
    For each lower precision in ``controls``, the same reading for the
    token that the pass in that precision puts first at each position of
    the same sequence."""
    tokens, idx, n = _padded(prompt, served)
    ref = logits_at(weights, tokens, idx, dm)[:n]
    best = ref.max(-1)

    def below(pick):
        return np.asarray(best - jnp.take_along_axis(ref, pick[:, None],
                                                     1)[:, 0])

    low = {c: below(jnp.argmax(logits_at(weights, tokens, idx, dm,
                                         low=c)[:n], -1))
           for c in controls}
    return below(jnp.asarray(served, jnp.int32)), low
