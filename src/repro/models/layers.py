"""Core model layers: norms, rotary embeddings, GLU MLP, attention.

Pure-functional JAX: parameters are pytrees of arrays; every layer is a
function ``(params, inputs) -> outputs``.  Training attention uses a chunked
online-softmax (flash-style) formulation so the compiled memory footprint is
O(S * chunk) rather than O(S^2) — this is also the pure-jnp oracle for the
Pallas kernel in ``repro.kernels``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# -- norms -------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dtype)


# -- rotary position embeddings ----------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32.

    Adjacent-pair (interleaved / NeoX) rotation: pair (2i, 2i+1) rotates by
    angle pos * theta^(-2i/hd).  Chosen over the half-split convention because
    rotation pairs stay contiguous — a head_dim-sharded tensor rotates fully
    locally under GSPMD (docs/design.md Sec. 5).
    """
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta), dtype=jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], hd // 2, 2)
    a, b = xp[..., 0], xp[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: tuple[int, int, int] = (2, 3, 3)) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the frequency bands are partitioned into
    (temporal, height, width) sections with independent position streams.

    x: (B, S, H, hd); positions: (B, S, 3) int32.
    """
    hd = x.shape[-1]
    half = hd // 2
    total = sum(sections)
    bounds = np.cumsum([int(round(half * s / total)) for s in sections])
    bounds[-1] = half
    freqs = jnp.asarray(rope_frequencies(hd, theta), dtype=jnp.float32)  # (half,)

    # For each frequency band, pick the position stream of its section.
    section_of_band = np.zeros(half, dtype=np.int32)
    section_of_band[bounds[0]:bounds[1]] = 1
    section_of_band[bounds[1]:] = 2
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.asarray(section_of_band)[None, None, :].repeat(positions.shape[0], 0)
        .repeat(positions.shape[1], 1),
        axis=-1)  # (B,S,half)
    angles = pos * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    a, b = xp[..., 0], xp[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# -- MLP ----------------------------------------------------------------------

def swiglu(params: dict, x: jax.Array) -> jax.Array:
    """params: w_gate (D,F), w_up (D,F), w_down (F,D)."""
    g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, params["w_down"])


def gelu_mlp(params: dict, x: jax.Array) -> jax.Array:
    """2-matrix GELU FFN (StarCoder2 / MusicGen style)."""
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(u), params["w_down"])


# -- attention -----------------------------------------------------------------

def _online_softmax_block(q, k, v, mask, carry):
    """One KV-chunk update of the online-softmax accumulator.

    q: (B,S,H,hd)  k/v: (B,C,Hkv,hd) already head-expanded to H.
    mask: (B,S,H,C) additive (0 or NEG_INF).
    carry: (acc (B,S,H,hd) f32, m (B,S,H) f32, l (B,S,H) f32)
    """
    acc, m, l = carry
    scores = jnp.einsum("bshd,bchd->bshc", q, k).astype(jnp.float32)
    scores = scores + mask
    m_new = jnp.maximum(m, scores.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bshc,bchd->bshd", p.astype(v.dtype), v).astype(jnp.float32)
    return acc_new, m_new, l_new


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_positions: jax.Array, kv_positions: jax.Array,
                    causal: bool = True, window: int = 0,
                    kv_chunk: int = 1024) -> jax.Array:
    """Chunked online-softmax attention (pure JAX flash formulation).

    q: (B,S,H,hd); k,v: (B,T,Hkv,hd); positions: (B,S)/(B,T) absolute.
    GQA: H must be a multiple of Hkv.  window>0 => sliding-window causal.
    Memory: O(S * kv_chunk) per head instead of O(S * T).
    """
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    groups = H // Hkv
    scale = hd ** -0.5
    q = (q * scale).astype(q.dtype)

    # Expand KV heads once per chunk inside the scan body (cheap view-like op).
    n_chunks = max(1, (T + kv_chunk - 1) // kv_chunk)
    pad = n_chunks * kv_chunk - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=np.iinfo(np.int32).max)
    k = k.reshape(B, n_chunks, kv_chunk, Hkv, hd)
    v = v.reshape(B, n_chunks, kv_chunk, Hkv, hd)
    kv_positions = kv_positions.reshape(B, n_chunks, kv_chunk)

    def body(carry, chunk):
        kc, vc, pc = chunk  # (B,C,Hkv,hd), (B,C,Hkv,hd), (B,C)
        kc = jnp.repeat(kc, groups, axis=2)
        vc = jnp.repeat(vc, groups, axis=2)
        valid = jnp.ones((B, S, 1, kc.shape[1]), dtype=bool)
        if causal:
            valid &= (q_positions[:, :, None, None] >= pc[:, None, None, :])
        if window:
            valid &= (q_positions[:, :, None, None] - pc[:, None, None, :]
                      < window)
        mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
        return _online_softmax_block(q, kc, vc, mask, carry), None

    init = (jnp.zeros((B, S, H, hd), jnp.float32),
            jnp.full((B, S, H), NEG_INF, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32))
    (acc, _, l), _ = jax.lax.scan(
        body, init,
        (k.transpose(1, 0, 2, 3, 4), v.transpose(1, 0, 2, 3, 4),
         kv_positions.transpose(1, 0, 2)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def paged_decode_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                           near_k: jax.Array, near_v: jax.Array,
                           meta: dict, mesh=None, layer=None) -> jax.Array:
    """Single-token attention through the fused paged tier (ISSUE 4).

    The TL-DRAM serving read path: instead of materializing the slot's far
    view and masking it, the Pallas kernel (`kernels.paged_attention`) walks
    the slot's page table in-kernel — one pool load per live, non-promoted
    page — and attends the shared near buffer under per-(slot, near-slot)
    live counts.  ``meta`` is `core.tiered_kv.paged_step_metadata`, computed
    once per decode step and shared by every layer.

    q: (B,1,H,hd); pool_k/pool_v: (P,page,Hkv,hd), or the stacked
    (L,P,page,Hkv,hd) pools with ``layer`` the layer to read;
    near: (C*page,Hkv,hd).
    Returns (B,1,H,hd), exactly standard attention over the live prefix.

    ``mesh``: KV-head-sharded pool/near buffers — the kernel runs per head
    shard under ``shard_map`` and the stats come back replicated
    (bit-identical to single-device; docs/design.md §2h).
    """
    from repro.kernels import ref
    from repro.kernels.paged_attention import paged_attention_stats
    stats = paged_attention_stats(q[:, 0], pool_k, pool_v, near_k, near_v,
                                  meta, mesh=mesh, layer=layer)
    return ref.merge_attention_stats([stats])[:, None].astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, window: int = 0) -> jax.Array:
    """Single-token attention against a (possibly ring-buffer) KV cache.

    q: (B,1,H,hd); caches: (B,T,Hkv,hd); pos: scalar int32 — the absolute
    position of the current token — or a ragged (B,) vector of per-sequence
    positions (continuous-batching slot pools).  With window>0 the cache is
    a ring buffer of size T=window whose slot for absolute position p is
    p % window.
    """
    B, _, H, hd = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    groups = H // Hkv
    scale = hd ** -0.5
    # f32-accumulated q·k scores (not bf16-rounded-then-cast): every decode
    # read path — this one, the tiered LSE merges, the fused paged kernel —
    # scores in f32, keeping cross-path logit noise at reduction-order
    # level.  preferred_element_type keeps the bf16 operands un-materialized
    # (bf16 MXU inputs, f32 accumulation); the scale is applied in f32.
    qh = q[:, 0].reshape(B, Hkv, groups, hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", qh, k_cache,
                        preferred_element_type=jnp.float32) * scale
    slots = jnp.arange(T)[None, :]                           # (1,T)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))[:, None]  # (B,1)
    if window:
        abs_pos = pos_b - ((pos_b - slots) % window)  # absolute pos per slot
        valid = ((abs_pos >= 0) & (abs_pos <= pos_b)
                 & (pos_b - abs_pos < window))
    else:
        valid = slots <= pos_b
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    # keep p in f32 for the value matmul: every decode read path (this
    # dense path, the two-tier LSE merges, the fused paged kernel)
    # accumulates p@v in f32, so cross-path logit noise stays at f32
    # reduction-order level (~1e-6) and fused-vs-dense token parity holds
    # bit-for-bit on real traces (tests/test_fused_serving.py)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)
