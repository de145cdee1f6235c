"""Pallas kernel: pool-native per-page attention-mass reduction.

The scoring pass (the paper's interval-sampled activation counts) used to
materialize every slot's full far view — the exact gather the fused decode
kernel (`kernels.paged_attention`) eliminated from the read path — just to
softmax it and sum per page.  With the pool as the single source of truth
(ISSUE 5) scoring walks the page table the same way the read does:

  grid (B,); per slot the kernel walks the slot's SCORE walk list (ALL
  mapped, live pages — near-resident pages included, so retention scores
  stay fresh; contrast the read walk, which skips promoted pages), issuing
  ONE async pool->VMEM copy of the whole (page, Hkv, hd) K page per entry
  and accumulating, for every KV head at once, an online-softmax numerator
  PER WALK ENTRY (an (Hkv, W) accumulator per GQA group, rescaled by the
  running max), so the per-page probability masses come out of one pass
  with no (B, T) score tensor and no far-view materialization.  Layout and
  tiling follow `kernels.paged_attention`.

Only ``pool_k`` is touched — masses need scores, not values — so the
scoring pass moves half the bytes of even a hypothetical fused read over
the same pages.

Returns (B, W) f32: per walk entry, the attention mass summed over ALL
query heads (callers divide by H and scatter entries back to slot-page
positions via the walk's ``score_j``).  ``paged_masses_ref`` is the
pure-jnp oracle the kernel is validated against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_masses_kernel(pid_ref, live_ref, len_ref, q_ref, pool_k_ref,
                         o_ref, kbuf, sem, *, page: int, W: int,
                         scale: float):
    b = pl.program_id(0)
    _, g, Hkv, hd = q_ref.shape
    qs = [q_ref[0, j].astype(jnp.float32) * scale for j in range(g)]
    row = jax.lax.broadcasted_iota(jnp.int32, (page, Hkv, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Hkv, W), 1)

    def body(i, carry):
        cp = pltpu.make_async_copy(pool_k_ref.at[pid_ref[b * W + i]], kbuf,
                                   sem)
        cp.start()
        cp.wait()
        kp = kbuf[...].astype(jnp.float32)                 # (page, Hkv, hd)
        alive = row < live_ref[b * W + i]
        new = []
        for j in range(g):
            psum, m, l = carry[j]
            s = jnp.sum(kp * qs[j][None], axis=-1, keepdims=True)
            s = jnp.where(alive, s, NEG_INF)                 # (page, Hkv, 1)
            m_new = jnp.maximum(m, jnp.max(s, axis=0))       # (Hkv, 1)
            alpha = jnp.exp(m - m_new)
            p = jnp.where(alive, jnp.exp(s - m_new[None]), 0.0)
            mass = jnp.sum(p, axis=0)                        # (Hkv, 1)
            # rescale every prior entry's numerator, then deposit this one's
            new.append((psum * alpha + jnp.where(col == i, mass, 0.0),
                        m_new, l * alpha + mass))
        return tuple(new)

    carry = tuple((jnp.zeros((Hkv, W), jnp.float32),
                   jnp.full((Hkv, 1), NEG_INF, jnp.float32),
                   jnp.zeros((Hkv, 1), jnp.float32)) for _ in range(g))
    carry = jax.lax.fori_loop(0, len_ref[b], body, carry)
    total = sum(psum / jnp.maximum(l, 1e-30) for psum, _, l in carry)
    o_ref[0] = jnp.sum(total, axis=0, keepdims=True)         # (1, W)


def paged_masses(q: jax.Array, pool_k: jax.Array, score_pid: jax.Array,
                 score_live: jax.Array, score_len: jax.Array,
                 interpret: bool = False, mesh=None) -> jax.Array:
    """Pool-native per-page attention masses.

    q: (B, H, hd) scoring queries (GQA: H a multiple of Hkv).
    pool_k: (P, page, Hkv, hd) shared far pool (stays in HBM/ANY).
    score_pid/score_live: (B, W) int32 — per slot, the pool ids of its
      mapped LIVE pages (front-packed, near-resident included) and each
      page's live row count; entries past ``score_len[b]`` unused.
    score_len: (B,) int32.

    Returns (B, W) f32: per walk entry, softmax attention mass summed over
    all H heads (entries past score_len are exactly zero).

    With a ``mesh`` whose 'model' axis divides Hkv the pool is
    KV-HEAD-SHARDED: the kernel runs per shard over its head slice (each
    head's softmax is independent) and the cross-head sum finishes with a
    ``psum`` over 'model'.  Unlike the read path this output IS a cross-
    head reduction, so its last bit may differ from the single-device sum
    order — masses drive page *placement* only, and emitted tokens are
    placement-invariant (the policy-parity pin)."""
    from repro.sharding.specs import kv_shard_count
    B, H, hd = q.shape
    _, page, Hkv, _ = pool_k.shape
    g = H // Hkv
    if mesh is not None and kv_shard_count(mesh, Hkv) > 1:
        from jax.sharding import PartitionSpec as P_

        def local_masses(q4, pk, s_pid, s_live, s_len):
            Hl = pk.shape[-2]
            out = paged_masses(q4.reshape(B, Hl * g, hd), pk, s_pid, s_live,
                               s_len, interpret=interpret)
            return jax.lax.psum(out, "model")

        sharded = jax.shard_map(
            local_masses, mesh=mesh,
            in_specs=(P_(None, "model"), P_(None, None, "model"),
                      P_(), P_(), P_()),
            out_specs=P_(),
            check_vma=False)
        return sharded(q.reshape(B, Hkv, g, hd), pool_k, score_pid,
                       score_live, score_len)
    W = score_pid.shape[1]
    flat = lambda a: jnp.asarray(a, jnp.int32).reshape(-1)
    # (B, g, Hkv, hd): each GQA group is one (Hkv, hd) panel
    qg = q.reshape(B, Hkv, g, hd).transpose(0, 2, 1, 3)

    kernel = functools.partial(_paged_masses_kernel, page=page, W=W,
                               scale=hd ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, g, Hkv, hd), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, W), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((page, Hkv, hd), pool_k.dtype),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        name="paged_masses",
        interpret=interpret,
    )(flat(score_pid), flat(score_live), flat(score_len), qg, pool_k)
    return out[:, 0]


def paged_masses_ref(q: jax.Array, pool_k: jax.Array, score_pid: jax.Array,
                     score_live: jax.Array,
                     score_len: jax.Array) -> jax.Array:
    """Materializing oracle: gather the walked pages, softmax, page-sum."""
    B, H, hd = q.shape
    P, page, Hkv, _ = pool_k.shape
    g = H // Hkv
    W = score_pid.shape[1]
    k = pool_k[score_pid]                         # (B, W, page, Hkv, hd)
    qh = q.reshape(B, Hkv, g, hd).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bkgd,bwpkd->bkgwp", qh, k.astype(jnp.float32))
    walk_ok = (jnp.arange(W)[None, :] < score_len[:, None])   # (B, W)
    alive = walk_ok[:, None, None, :, None] & \
        (jnp.arange(page)[None, None, None, None, :]
         < score_live[:, None, None, :, None])
    s = jnp.where(alive, s, NEG_INF)
    flat = s.reshape(B, Hkv, g, W * page)
    p = jax.nn.softmax(flat, axis=-1).reshape(s.shape)
    p = jnp.where(alive, p, 0.0)
    return p.sum(axis=(1, 2, 4))                  # (B, W)
