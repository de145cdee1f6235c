"""Unified decoder for every assigned architecture family.

One parameter layout, one scan-over-layers apply, four block flavours:

  dense / vlm / audio : attn + SwiGLU
  moe                 : attn + MoE (EP-shardable dispatch)
  ssm                 : SSD mixer only (attention-free)
  hybrid (Hymba)      : parallel attn + SSD heads, merged, then SwiGLU

Layers are stacked along a leading L axis and applied with ``jax.lax.scan``
(small HLO, O(1) compile cost in depth) with configurable rematerialization.
Decode uses an explicit cache pytree; sliding-window attention uses a ring
buffer of size `window` so the 500k-token shapes keep O(window) KV state.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.sharding import ctx
from repro.models.layers import (
    apply_mrope, apply_rope, decode_attention, flash_attention, gelu_mlp,
    paged_decode_attention, rms_norm, swiglu,
)

Params = dict
Cache = dict


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _init_attn(key, arch: ArchConfig, dtype):
    hd = arch.resolved_head_dim
    D, H, Hkv = arch.d_model, arch.n_heads, arch.n_kv_heads
    ks = jax.random.split(key, 4)
    s = D ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (D, H, hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (D, Hkv, hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (D, Hkv, hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (H, hd, D)) * (H * hd) ** -0.5
               ).astype(dtype),
    }
    if arch.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _init_mlp(key, arch: ArchConfig, dtype):
    D, F = arch.d_model, arch.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_up": (jax.random.normal(ks[1], (D, F)) * D ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (F, D)) * F ** -0.5).astype(dtype),
    }
    if arch.mlp_gated:
        p["w_gate"] = (jax.random.normal(ks[0], (D, F)) * D ** -0.5
                       ).astype(dtype)
    return p


def _init_layer(key, arch: ArchConfig, dtype):
    ks = jax.random.split(key, 6)
    D = arch.d_model
    p: dict = {}
    if arch.family == "ssm":
        p["ssm_norm"] = jnp.ones((D,), dtype)
        p["ssm"] = ssm_lib.init_ssm_params(ks[0], D, arch.ssm, dtype)
        return p
    p["attn_norm"] = jnp.ones((D,), dtype)
    p["attn"] = _init_attn(ks[0], arch, dtype)
    if arch.family == "hybrid":
        p["ssm"] = ssm_lib.init_ssm_params(ks[1], D, arch.ssm, dtype)
        p["attn_out_norm"] = jnp.ones((D,), dtype)
        p["ssm_out_norm"] = jnp.ones((D,), dtype)
    p["mlp_norm"] = jnp.ones((D,), dtype)
    if arch.family == "moe":
        p["moe"] = moe_lib.init_moe_params(ks[2], D, arch.moe, dtype)
    else:
        p["mlp"] = _init_mlp(ks[3], arch, dtype)
    return p


def init_params(key: jax.Array, arch: ArchConfig,
                dtype=jnp.float32) -> Params:
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    V, D, L = arch.vocab, arch.d_model, arch.n_layers
    layer_keys = jax.random.split(k_layers, L)
    layers = jax.vmap(lambda k: _init_layer(k, arch, dtype))(layer_keys)
    p = {
        "embed": (jax.random.normal(k_embed, (V, D)) * 0.02).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype),
    }
    if arch.family == "audio":
        p["lm_head"] = (jax.random.normal(k_head, (arch.n_codebooks, D, V))
                        * D ** -0.5).astype(dtype)
    elif not arch.tie_embeddings:
        p["lm_head"] = (jax.random.normal(k_head, (D, V)) * D ** -0.5
                        ).astype(dtype)
    return p


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _attn_apply(p, x, positions, arch: ArchConfig, kv_override=None,
                decode_cache=None, pos_scalar=None, kv_prefix=None):
    """Full attention path.  Returns (out, (k, v)) for cache construction.

    kv_prefix: optional (k_pre, v_pre, pre_positions) — already-computed
    (RoPE-rotated) K/V of a shared prompt prefix; queries attend the prefix
    plus themselves (chunked prefill for the prefix-sharing admission path).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if arch.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if arch.mrope:
        q = apply_mrope(q, positions, arch.rope_theta)
        k = apply_mrope(k, positions, arch.rope_theta)
        pos_1d = positions[..., 0]
    else:
        q = apply_rope(q, positions, arch.rope_theta)
        k = apply_rope(k, positions, arch.rope_theta)
        pos_1d = positions
    if decode_cache is not None:
        k_cache, v_cache = decode_cache
        out = decode_attention(q, k_cache, v_cache, pos_scalar,
                               window=arch.sliding_window)
    elif kv_prefix is not None:
        k_pre, v_pre, pre_pos = kv_prefix
        k_all = jnp.concatenate([k_pre.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([v_pre.astype(v.dtype), v], axis=1)
        kv_pos = jnp.concatenate([pre_pos, pos_1d], axis=1)
        out = flash_attention(q, k_all, v_all, pos_1d, kv_pos, causal=True,
                              window=arch.sliding_window)
    else:
        out = flash_attention(q, k, v, pos_1d, pos_1d, causal=True,
                              window=arch.sliding_window)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, (k, v)


def _block_train(p, x, positions, arch: ArchConfig, kv_prefix=None):
    """One layer, training/prefill mode.  Returns (x, aux, (k, v), ssm_state,
    conv_tail) — cache parts are None where inapplicable."""
    aux = jnp.float32(0.0)
    kv = ssm_state = conv_tail = None
    if arch.family == "ssm":
        h, ssm_state, conv_tail = ssm_lib.ssd_chunked(
            p["ssm"], rms_norm(x, p["ssm_norm"]), arch.ssm)
        return x + h, aux, kv, ssm_state, conv_tail

    normed = rms_norm(x, p["attn_norm"])
    attn_out, kv = _attn_apply(p["attn"], normed, positions, arch,
                               kv_prefix=kv_prefix)
    if arch.family == "hybrid":
        ssm_out, ssm_state, conv_tail = ssm_lib.ssd_chunked(
            p["ssm"], normed, arch.ssm)
        mixed = 0.5 * (rms_norm(attn_out, p["attn_out_norm"])
                       + rms_norm(ssm_out, p["ssm_out_norm"]))
        x = x + mixed
    else:
        x = x + attn_out

    normed2 = rms_norm(x, p["mlp_norm"])
    if arch.family == "moe":
        mlp_out, aux = moe_lib.moe_block(p["moe"], normed2, arch.moe)
    elif arch.mlp_gated:
        mlp_out = swiglu(p["mlp"], normed2)
    else:
        mlp_out = gelu_mlp(p["mlp"], normed2)
    return x + mlp_out, aux, kv, ssm_state, conv_tail


def _block_decode(p, x, cache_layer, pos, arch: ArchConfig, kv_hook=None):
    """One layer, single-token decode.  cache_layer is this layer's slice.

    ``pos`` is a scalar position, or a ragged (B,) vector of per-sequence
    positions (the continuous-batching slot pool).  Returns
    (h, new_cache, q) — q is this layer's rotated query (attention
    families; None for ssm), used by the serving engine's tier scoring.

    ``kv_hook(q, k, v, cache_layer) -> (attn, cache_updates)``: optional
    override of the KV write + attend section (rotated q/k/v in, attention
    output out) — the fused paged tier routes through here
    (``paged_decode_step``) while RoPE/MLP/norm stay shared.
    """
    new_cache = dict(cache_layer)
    ragged = jnp.asarray(pos).ndim == 1
    if ragged:
        positions = pos[:, None]
        if arch.mrope:
            positions = jnp.broadcast_to(positions[..., None],
                                         (x.shape[0], 1, 3))
    else:
        positions = jnp.broadcast_to(pos, (x.shape[0], 1))
        if arch.mrope:
            positions = jnp.broadcast_to(pos, (x.shape[0], 1, 3))

    if arch.family == "ssm":
        h, state, conv = ssm_lib.ssd_decode_step(
            p["ssm"], rms_norm(x, p["ssm_norm"]),
            cache_layer["ssm"], cache_layer["conv"], arch.ssm)
        new_cache.update(ssm=state, conv=conv)
        return x + h, new_cache, None

    normed = rms_norm(x, p["attn_norm"])
    # write the new token's K/V into the cache slot, then attend
    q = jnp.einsum("bsd,dhk->bshk", normed, p["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", normed, p["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", normed, p["attn"]["wv"])
    if arch.qk_norm:
        q = rms_norm(q, p["attn"]["q_norm"])
        k = rms_norm(k, p["attn"]["k_norm"])
    if arch.mrope:
        q = apply_mrope(q, positions, arch.rope_theta)
        k = apply_mrope(k, positions, arch.rope_theta)
    else:
        q = apply_rope(q, positions, arch.rope_theta)
        k = apply_rope(k, positions, arch.rope_theta)
    if kv_hook is not None:
        out, cache_updates = kv_hook(q, k, v, cache_layer)
        new_cache.update(cache_updates)
    else:
        T = cache_layer["k"].shape[1]
        slot = pos % T if arch.sliding_window else jnp.minimum(pos, T - 1)
        if ragged:
            b_idx = jnp.arange(x.shape[0])
            k_cache = cache_layer["k"].at[b_idx, slot].set(k[:, 0])
            v_cache = cache_layer["v"].at[b_idx, slot].set(v[:, 0])
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache_layer["k"], k, slot, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache_layer["v"], v, slot, 1)
        new_cache.update(k=k_cache, v=v_cache)
        out = decode_attention(q, k_cache, v_cache, pos,
                               window=arch.sliding_window)
    attn_out = jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"])

    if arch.family == "hybrid":
        ssm_out, state, conv = ssm_lib.ssd_decode_step(
            p["ssm"], normed, cache_layer["ssm"], cache_layer["conv"], arch.ssm)
        new_cache.update(ssm=state, conv=conv)
        mixed = 0.5 * (rms_norm(attn_out, p["attn_out_norm"])
                       + rms_norm(ssm_out, p["ssm_out_norm"]))
        x = x + mixed
    else:
        x = x + attn_out

    normed2 = rms_norm(x, p["mlp_norm"])
    if arch.family == "moe":
        # decode: tiny token counts => lossless capacity (no token dropping)
        mlp_out, _ = moe_lib.moe_block(p["moe"], normed2, arch.moe,
                                       group_size=x.shape[0], no_drop=True)
    elif arch.mlp_gated:
        mlp_out = swiglu(p["mlp"], normed2)
    else:
        mlp_out = gelu_mlp(p["mlp"], normed2)
    return x + mlp_out, new_cache, q


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch: dict, arch: ArchConfig) -> jax.Array:
    if arch.family == "audio":
        return batch["frame_embeds"]
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if arch.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        n_patch = pe.shape[1]
        x = jnp.concatenate([pe, x[:, n_patch:]], axis=1)
    return x


def _positions_for(batch: dict, arch: ArchConfig, seq: int, bsz: int):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (bsz, seq))
    if arch.mrope:
        pos = jnp.broadcast_to(pos[..., None], (bsz, seq, 3))
    return pos


def _lm_logits(params, x, arch: ArchConfig):
    if arch.family == "audio":
        return jnp.einsum("bsd,kdv->bskv", x, params["lm_head"])
    head = params.get("lm_head", params["embed"].T)
    return jnp.einsum("bsd,dv->bsv", x, head)


def forward(params: Params, batch: dict, arch: ArchConfig,
            remat: str = "full", compute_dtype=jnp.bfloat16):
    """Training/scoring forward: returns (logits, aux_loss)."""
    x = _embed_inputs(params, batch, arch).astype(compute_dtype)
    x = ctx.constrain(x, ctx.BATCH, ctx.SEQ, None)
    B, S = x.shape[:2]
    positions = _positions_for(batch, arch, S, B)

    cparams = jax.tree.map(
        lambda a: a.astype(compute_dtype)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params["layers"])

    def body(carry, layer_params):
        h, aux = carry
        h = ctx.constrain(h, ctx.BATCH, ctx.SEQ, None)
        h, aux_l, *_ = _block_train(layer_params, h, positions, arch)
        h = ctx.constrain(h, ctx.BATCH, ctx.SEQ, None)
        return (h, aux + aux_l), None

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), cparams)
    x = rms_norm(x, params["final_norm"].astype(compute_dtype))
    logits = _lm_logits(params, x, arch)
    logits = ctx.constrain(logits, ctx.BATCH,
                           *([None] * (logits.ndim - 2)), ctx.MODEL)
    return logits, aux / max(arch.n_layers, 1)


def loss_fn(params: Params, batch: dict, arch: ArchConfig,
            remat: str = "full", aux_weight: float = 0.01):
    logits, aux = forward(params, batch, arch, remat=remat)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask", jnp.ones_like(nll))
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss + aux_weight * aux, {"nll": loss, "aux": aux}


# -- cache ---------------------------------------------------------------------

def init_cache(arch: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Cache:
    """Decode cache pytree; leaves have a leading L axis for the layer scan."""
    L = arch.n_layers
    c: Cache = {"pos": jnp.zeros((), jnp.int32)}
    if arch.n_heads:
        T = min(max_len, arch.sliding_window) if arch.sliding_window else max_len
        hd = arch.resolved_head_dim
        c["k"] = jnp.zeros((L, batch, T, arch.n_kv_heads, hd), dtype)
        c["v"] = jnp.zeros((L, batch, T, arch.n_kv_heads, hd), dtype)
    if arch.ssm is not None:
        s = arch.ssm
        c["ssm"] = jnp.zeros((L, batch, s.n_heads, s.head_dim, s.d_state),
                             jnp.float32)
        c["conv"] = (
            jnp.zeros((L, batch, s.d_conv - 1, ssm_lib.d_inner(s)), dtype),
            jnp.zeros((L, batch, s.d_conv - 1, 2 * s.d_state), dtype))
    return c


def prefill(params: Params, batch: dict, arch: ArchConfig, max_len: int,
            compute_dtype=jnp.bfloat16, prefix_kv=None):
    """Process a prompt, returning (logits, cache ready for decode).

    prefix_kv: optional (k_pre, v_pre) of shape (L, B, T_pre, Hkv, hd) —
    already-computed K/V of a shared prompt prefix (the paged far pool's
    copy).  Only the *suffix* in ``batch`` is computed; its queries attend
    prefix + suffix, and the returned cache holds prefix followed by suffix
    rows — exactly the cache a full prefill of prefix+suffix would produce,
    at suffix cost.  ``batch["positions"]`` must then carry the suffix's
    absolute positions (T_pre + arange(S)); logits cover the suffix only.
    """
    x = _embed_inputs(params, batch, arch).astype(compute_dtype)
    x = ctx.constrain(x, ctx.BATCH, ctx.SEQ, None)
    B, S = x.shape[:2]
    positions = _positions_for(batch, arch, S, B)
    cache = init_cache(arch, B, max_len, compute_dtype)

    cparams = jax.tree.map(
        lambda a: a.astype(compute_dtype)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params["layers"])

    t_pre = 0
    if prefix_kv is not None:
        assert arch.n_heads and arch.ssm is None and not arch.sliding_window, \
            "prefix-chunked prefill needs a plain-attention architecture"
        k_pre, v_pre = prefix_kv
        t_pre = k_pre.shape[2]
        pre_pos = jnp.broadcast_to(jnp.arange(t_pre, dtype=jnp.int32),
                                   (B, t_pre))
        xs = (cparams, k_pre.astype(compute_dtype),
              v_pre.astype(compute_dtype))
    else:
        xs = (cparams, None, None)

    def body(h, scanned):
        layer_params, k_pre_l, v_pre_l = scanned
        kv_prefix = None if k_pre_l is None \
            else (k_pre_l, v_pre_l, pre_pos)
        h = ctx.constrain(h, ctx.BATCH, ctx.SEQ, None)
        h, _, kv, ssm_state, conv_tail = _block_train(
            layer_params, h, positions, arch, kv_prefix=kv_prefix)
        h = ctx.constrain(h, ctx.BATCH, ctx.SEQ, None)
        outs = {}
        if kv is not None:
            k, v = kv
            if k_pre_l is not None:
                k = jnp.concatenate([k_pre_l.astype(k.dtype), k], axis=1)
                v = jnp.concatenate([v_pre_l.astype(v.dtype), v], axis=1)
            written = k.shape[1]
            T = cache["k"].shape[2]
            if arch.sliding_window and written > T:
                # Keep the last `window` tokens, rotated into ring order.
                k, v = k[:, -T:], v[:, -T:]
                shift = written % T
                k = jnp.roll(k, shift, axis=1)
                v = jnp.roll(v, shift, axis=1)
            elif written < T:
                k = jnp.pad(k, ((0, 0), (0, T - written), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, T - written), (0, 0), (0, 0)))
            outs["k"], outs["v"] = k, v
        if ssm_state is not None:
            outs["ssm"] = ssm_state
            outs["conv"] = conv_tail
        return h, outs

    x, stacked = jax.lax.scan(body, x, xs)
    x = rms_norm(x, params["final_norm"].astype(compute_dtype))
    logits = _lm_logits(params, x, arch)
    logits = ctx.constrain(logits, ctx.BATCH,
                           *([None] * (logits.ndim - 2)), ctx.MODEL)
    cache = {**cache, **stacked, "pos": jnp.asarray(t_pre + S, jnp.int32)}
    return logits, cache


def decode_step(params: Params, cache: Cache, batch: dict, arch: ArchConfig,
                compute_dtype=jnp.bfloat16, want_aux: bool = False):
    """One decode step.  batch['tokens']: (B, 1) (or frame_embeds (B,1,D)).

    ``cache['pos']`` may be a scalar (whole batch at one position) or a
    ragged (B,) vector of per-sequence positions (continuous-batching slot
    pools; each sequence attends its own live prefix and writes its K/V at
    its own slot).

    Returns (logits (B,1,V...), new cache); with ``want_aux=True`` also a
    third aux dict with ``q0`` — layer-0's rotated query (B,H,hd), the
    probe the tiered-KV scoring pass uses (attention families only)."""
    x = _embed_inputs(params, batch, arch).astype(compute_dtype)
    x = ctx.constrain(x, ctx.BATCH, ctx.SEQ, None)
    pos = cache["pos"]

    cparams = jax.tree.map(
        lambda a: a.astype(compute_dtype)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params["layers"])

    layer_cache = {k: v for k, v in cache.items() if k != "pos"}

    def body(h, scanned):
        layer_params, cl = scanned
        h = ctx.constrain(h, ctx.BATCH, ctx.SEQ, None)
        h, new_cl, q = _block_decode(layer_params, h, cl, pos, arch)
        h = ctx.constrain(h, ctx.BATCH, ctx.SEQ, None)
        return h, (new_cl, q if want_aux else None)

    x, (new_layer_cache, qs) = jax.lax.scan(body, x, (cparams, layer_cache))
    x = rms_norm(x, params["final_norm"].astype(compute_dtype))
    logits = _lm_logits(params, x, arch)
    logits = ctx.constrain(logits, ctx.BATCH,
                           *([None] * (logits.ndim - 2)), ctx.MODEL)
    new_cache = {**new_layer_cache, "pos": pos + 1}
    if want_aux:
        aux = {"q0": qs[0][:, 0].astype(jnp.float32)} if qs is not None else {}
        return logits, new_cache, aux
    return logits, new_cache


def paged_decode_step(params: Params, cache: Cache, batch: dict,
                      arch: ArchConfig, meta: dict,
                      compute_dtype=jnp.bfloat16, want_aux: bool = False,
                      fused: bool = True, mesh=None):
    """One decode step over the paged tier — the pool is the ONLY KV store.

    Identical math to ``decode_step`` — every layer attends its slot's full
    live prefix — but the per-layer shared page pool is the single source
    of truth (ISSUE 5): the new token's K/V is written through the page
    table into the pool (``append_pid``/``append_off``; sentinel drops) and
    NOWHERE else — the dense per-slot master rows of the PR-4 path are
    gone.  Two read paths over the same pool bytes:

      fused=True  : the page-table-walking kernel (`kernels.paged_attention`)
                    over pool + per-layer global near buffer — touches only
                    each slot's live, non-promoted far pages.
      fused=False : materialize the slot's far view from the pool per layer
                    and run the same ``decode_attention`` reduction the
                    PR-4 dense-master path ran — bit-identical logits to
                    it, since the pool holds bit-identical bytes (the
                    oracle leg of the fused-vs-dense token-parity pin).

    ``cache`` carries:

      pool_k/pool_v : (L, P, page, Hkv, hd)  per-layer shared far pool
      near_k/near_v : (L, C*page, Hkv, hd)   per-layer global near buffer
                                             (read only by the fused path)

    ``meta`` is ``core.tiered_kv.paged_step_metadata(state, pos + 1,
    cfg, append_pos=pos)`` — computed ONCE per step by the engine and shared
    by every layer (lengths = pos + 1 so the token appended this step is
    attended, matching ``decode_attention``'s ``slot <= pos`` mask).

    ``mesh``: pool/near buffers KV-HEAD-SHARDED over the 'model' axis
    (docs/design.md §2h).  The append scatter indexes only (page, offset)
    dims, so it partitions under GSPMD with exact semantics; the fused read
    runs per head shard under ``shard_map`` and hands back replicated
    stats; the dense read computes per-head stats under GSPMD (head-local
    math — no collective can reorder it) and the attention output is
    CONSTRAINED replicated before the wo projection, so the cross-head
    contraction always reduces the full head dim in single-device order —
    the bit-identity pin.  Emitted tokens are bit-identical to the
    single-device step in both modes (tests/test_mesh_serving.py).

    Returns (logits, new_cache[, aux]) like ``decode_step``.
    """
    assert arch.n_heads and arch.ssm is None and not arch.sliding_window, \
        "paged decode requires a plain-attention architecture"
    from repro.sharding.specs import kv_shard_count
    if mesh is not None and kv_shard_count(mesh, arch.n_kv_heads) == 1:
        mesh = None                   # GQA/MQA fallback: fully replicated
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P_
        _pool_ns = NamedSharding(mesh, P_(None, None, None, "model"))
        _repl_ns = NamedSharding(mesh, P_())
    x = _embed_inputs(params, batch, arch).astype(compute_dtype)
    x = ctx.constrain(x, ctx.BATCH, ctx.SEQ, None)
    pos = cache["pos"]
    if jnp.asarray(pos).ndim == 0:
        pos = jnp.broadcast_to(pos, (x.shape[0],))
    B = x.shape[0]

    cparams = jax.tree.map(
        lambda a: a.astype(compute_dtype)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params["layers"])
    n_pages = meta["pt"].shape[1]
    safe = jnp.maximum(meta["pt"], 0)

    def body(carry, scanned):
        # the stacked pools ride in the CARRY and each layer writes its
        # appended row in place (a donated pool is then updated without a
        # second pool-sized buffer); the read-only near buffers are scanned
        h, pool_k, pool_v = carry
        layer_params, nk, nv, layer = scanned

        def kv_hook(q, k, v, _):
            at = (layer, meta["append_pid"], meta["append_off"])
            pk = pool_k.at[at].set(k[:, 0], mode="drop")
            pv = pool_v.at[at].set(v[:, 0], mode="drop")
            if mesh is not None:
                # keep the appended pool head-sharded (the scatter touches
                # only layer/page/offset dims — GSPMD must not drift the
                # pool to replicated across steps)
                pk = jax.lax.with_sharding_constraint(pk, _pool_ns)
                pv = jax.lax.with_sharding_constraint(pv, _pool_ns)
            if fused:
                out = paged_decode_attention(q, pk, pv, nk, nv, meta,
                                             mesh=mesh, layer=layer)
            else:
                _, _, page, Hkv, hd = pk.shape
                k_view = pk[layer, safe].reshape(B, n_pages * page, Hkv, hd)
                v_view = pv[layer, safe].reshape(B, n_pages * page, Hkv, hd)
                out = decode_attention(q, k_view, v_view, pos)
                if mesh is not None:
                    # per-head stats are exact under GSPMD (no op crosses
                    # heads); replicate them HERE so the wo contraction
                    # reduces the full head dim in single-device order
                    # instead of a GSPMD partial-sum psum — the dense
                    # path's bit-identity pin
                    out = jax.lax.with_sharding_constraint(out, _repl_ns)
            return out, {"pool_k": pk, "pool_v": pv}

        h = ctx.constrain(h, ctx.BATCH, ctx.SEQ, None)
        h, pools, q = _block_decode(layer_params, h, {}, pos, arch,
                                    kv_hook=kv_hook)
        h = ctx.constrain(h, ctx.BATCH, ctx.SEQ, None)
        return (h, pools["pool_k"], pools["pool_v"]), \
            q if want_aux else None

    layers = jnp.arange(arch.n_layers, dtype=jnp.int32)
    (x, pool_k, pool_v), qs = jax.lax.scan(
        body, (x, cache["pool_k"], cache["pool_v"]),
        (cparams, cache["near_k"], cache["near_v"], layers))
    x = rms_norm(x, params["final_norm"].astype(compute_dtype))
    logits = _lm_logits(params, x, arch)
    logits = ctx.constrain(logits, ctx.BATCH,
                           *([None] * (logits.ndim - 2)), ctx.MODEL)
    new_cache = {"pool_k": pool_k, "pool_v": pool_v,
                 "near_k": cache["near_k"], "near_v": cache["near_v"],
                 "pos": cache["pos"] + 1}
    if want_aux:
        aux = {"q0": qs[0][:, 0].astype(jnp.float32)}
        return logits, new_cache, aux
    return logits, new_cache
