"""Pallas flash-decode kernel that walks the page table INSIDE the kernel.

The paged far tier's read path used to materialize every slot's full far
view — a `(B, n_pages*page, Hkv, hd)` gather per decode step per layer —
before attending it, touching `n_pages*page` rows per slot regardless of how
few pages were actually live.  TL-DRAM's far segment is accessed *in place*
through the isolation transistor: cost is paid per access, never per bit of
the segment (PAPER.md §3).  This kernel applies that economics to the
gather path itself:

  grid (B,); per slot the kernel, for ALL KV heads at once,
    1. attends the shared NEAR buffer (VMEM-resident: its block index never
       changes, so it is fetched once per call, not once per slot) under
       per-(slot, near-slot) live counts — the global near tier serves
       every tenant of a promoted page with its own position mask;
    2. walks the slot's compacted page-table WALK LIST in blocks of
       `kb = 128 // page` pages (8 at the serving page of 16) through two
       VMEM block buffers: one async pool->VMEM copy of the whole
       `(page, Hkv, hd)` page (contiguous in HBM) per *mapped,
       non-promoted, live* page, block i+1's copies started before block
       i is computed (block 0's fly during the near pass), and one
       online-softmax update — one running-max rescale — per block, each
       row masked by its page's partial-last-page live count.  Entries
       past `walk_len` are never copied; their rows in the buffer are
       stale or uninitialised, so they are masked out of the scores and
       zeroed in V.

  Far bytes touched per step per slot == sum of live, non-promoted page
  rows — never `n_pages * page` (asserted end-to-end by the serving
  accounting in BENCH_serving.json).

Layout (docs/design.md §2e): the pool keeps its `(P, page, Hkv, hd)` layout,
so the `(Hkv, hd)` minor dims of every block are whole arrays and satisfy the
TPU's (8, 128) tiling rule at any head count.  Scores and the p·v update are
VPU multiply + reduce over `hd` (lanes) and `page` (leading dim) in f32; the
per-slot ints (walk list, live counts, near live counts) ride in SMEM via
scalar prefetch.  Queries enter as `(B, g, Hkv, hd)` so each GQA group is one
`(Hkv, hd)` panel; m/l leave lane-replicated as `(B, g, Hkv, 128)`.

Returns *unnormalized* `(out, m, l)` online-softmax stats, the same
contract as `kernels.tiered_attention`, so callers can LSE-merge with other
partial results exactly.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30
LANES = 128                     # m/l leave the kernel lane-replicated
BLOCK_ROWS = 128                # far rows per block buffer (kb = 128 // page)


def paged_attention_stats(q: jax.Array, pool_k: jax.Array,
                          pool_v: jax.Array, near_k: jax.Array,
                          near_v: jax.Array, meta: dict, mesh=None,
                          layer=None):
    """Run the fused kernel from a ``paged_step_metadata`` dict — the one
    entry point both the serving decode step and the core read path /
    verification probe share.  ``layer``: with stacked ``(L, P, page, Hkv,
    hd)`` pools, the layer whose pages the walk reads (see
    ``paged_attention``).

    With a ``mesh`` whose 'model' axis divides Hkv, the pool and near
    buffers are KV-HEAD-SHARDED and the kernel runs under ``shard_map``:
    each device walks only its head slice of every mapped page (GSPMD
    cannot partition a ``pallas_call``, so the shard boundary is explicit).
    The per-head math is untouched — no arithmetic crosses heads — so a
    tiled ``all_gather`` of the per-head stats over 'model' returns
    REPLICATED (out, m, l) bit-identical to the single-device call, and
    every cross-head reduction downstream (the wo projection, the LSE
    merge) sees the full head dim in single-device order.  Head counts
    that do not divide the axis fall back to the replicated single-device
    call (``kv_shard_count``)."""
    from repro.sharding.specs import kv_shard_count
    walk = (meta["walk_pid"], meta["walk_live"], meta["walk_len"],
            meta["near_live"])
    Hkv = pool_k.shape[-2]
    if kv_shard_count(mesh, Hkv) == 1:
        return paged_attention(q, pool_k, pool_v, near_k, near_v, *walk,
                               layer=layer, interpret=interpret_mode())

    from jax.sharding import PartitionSpec as P
    B, H, hd = q.shape
    g = H // Hkv

    def local_stats(q4, pk, pv, nk, nv, w_pid, w_live, w_len, n_live):
        Hl = pk.shape[-2]                       # this shard's kv heads
        out, m, l = paged_attention(q4.reshape(B, Hl * g, hd), pk, pv,
                                    nk, nv, w_pid, w_live, w_len, n_live,
                                    layer=layer, interpret=interpret_mode())
        gather = functools.partial(jax.lax.all_gather, axis_name="model",
                                   axis=1, tiled=True)
        return (gather(out.reshape(B, Hl, g, hd)).reshape(B, H, hd),
                gather(m.reshape(B, Hl, g)).reshape(B, H),
                gather(l.reshape(B, Hl, g)).reshape(B, H))

    head = P(None, "model")                     # dim ndim-2 = Hkv
    pool = P(*[None] * (pool_k.ndim - 2), "model")
    sharded = jax.shard_map(
        local_stats, mesh=mesh,
        in_specs=(P(None, "model"), pool, pool, head, head,
                  P(), P(), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False)                        # pallas body: no vma tracking
    return sharded(q.reshape(B, Hkv, g, hd), pool_k, pool_v, near_k, near_v,
                   *walk)


def _paged_attention_kernel(layer_ref, walk_pid_ref, walk_live_ref,
                            walk_len_ref, near_live_ref, q_ref, nk_ref,
                            nv_ref, pool_k_ref, pool_v_ref, o_ref, m_ref,
                            l_ref, kbuf, vbuf, sem, *, page: int,
                            n_near: int, W: int, kb: int, scale: float,
                            stacked: bool):
    b = pl.program_id(0)
    if stacked:                         # (L, P, page, Hkv, hd) pools
        pool_k_ref = pool_k_ref.at[layer_ref[0]]
        pool_v_ref = pool_v_ref.at[layer_ref[0]]
    _, g, Hkv, hd = q_ref.shape
    qs = [q_ref[0, j].astype(jnp.float32) * scale for j in range(g)]
    row = jax.lax.broadcasted_iota(jnp.int32, (page, Hkv, 1), 0)

    def update(carry, load, lives):
        """One online-softmax update over ``len(lives)`` pages for every
        (group, head): one running-max rescale per block, not per page.
        ``load(p)`` gives page p's (page, Hkv, hd) K and V rows; rows at or
        past ``lives[p]`` are dead and may hold stale or uninitialised
        bytes, so they are kept out of the scores AND zeroed in V
        (0 * NaN is NaN)."""
        alive = [row < live for live in lives]
        kvs = [load(p) for p in range(len(lives))]
        ks = [k.astype(jnp.float32) for k, _ in kvs]
        vs = [jnp.where(a, v.astype(jnp.float32), 0.0)
              for a, (_, v) in zip(alive, kvs)]
        new = []
        for j in range(g):
            acc, m, l = carry[j]
            s = [jnp.where(a, jnp.sum(k * qs[j][None], axis=-1,
                                      keepdims=True), NEG_INF)
                 for a, k in zip(alive, ks)]            # (page, Hkv, 1) each
            m_new = m
            for sp in s:
                m_new = jnp.maximum(m_new, jnp.max(sp, axis=0))  # (Hkv, 1)
            alpha = jnp.exp(m - m_new)
            acc, l = acc * alpha, l * alpha
            for a, sp, v in zip(alive, s, vs):
                p = jnp.where(a, jnp.exp(sp - m_new[None]), 0.0)
                acc = acc + jnp.sum(p * v, axis=0)
                l = l + jnp.sum(p, axis=0)
            new.append((acc, m_new, l))
        return tuple(new)

    carry = tuple((jnp.zeros((Hkv, hd), jnp.float32),
                   jnp.full((Hkv, 1), NEG_INF, jnp.float32),
                   jnp.zeros((Hkv, 1), jnp.float32)) for _ in range(g))

    # -- far copies: the slot's live, non-promoted pages, kb pages a block
    #    in two block buffers; block 0 flies while the near pass computes --
    n = walk_len_ref[b]

    def copies(blk, slot, p):
        pid = walk_pid_ref[b * W + blk * kb + p]
        rows = pl.ds(p * page, page)
        return (pltpu.make_async_copy(pool_k_ref.at[pid],
                                      kbuf.at[slot, rows], sem.at[0, slot, p]),
                pltpu.make_async_copy(pool_v_ref.at[pid],
                                      vbuf.at[slot, rows], sem.at[1, slot, p]))

    def each_live_page(blk, slot, op):
        """Run ``op`` on both copies of every page of block ``blk`` that is
        on the walk; pages past ``walk_len`` are never copied."""
        for p in range(kb):
            @pl.when(blk * kb + p < n)
            def _(p=p):
                for cp in copies(blk, slot, p):
                    op(cp)

    each_live_page(0, 0, lambda cp: cp.start())

    # -- near pass: C resident VMEM pages, in blocks of kn pages --------------
    kn = math.gcd(n_near, kb)           # divides C: no block runs past it

    def near_body(i, carry):
        def load(p):
            rows = pl.ds((i * kn + p) * page, page)
            return nk_ref[rows], nv_ref[rows]
        return update(carry, load, [near_live_ref[b * n_near + i * kn + p]
                                    for p in range(kn)])

    carry = jax.lax.fori_loop(0, n_near // kn, near_body, carry)

    # -- far pass: block i+1's copies fly while block i computes -------------
    def far_block(i, carry):
        slot = i % 2
        each_live_page(i + 1, 1 - slot, lambda cp: cp.start())
        each_live_page(i, slot, lambda cp: cp.wait())
        lives = []
        for p in range(kb):
            e = i * kb + p                  # past W only when past n
            lives.append(jnp.where(
                e < n, walk_live_ref[b * W + jnp.minimum(e, W - 1)], 0))

        def load(p):
            rows = pl.ds(p * page, page)
            return kbuf[slot, rows], vbuf[slot, rows]
        return update(carry, load, lives)

    carry = jax.lax.fori_loop(0, pl.cdiv(n, kb), far_block, carry)

    for j in range(g):
        acc, m, l = carry[j]
        o_ref[0, j] = acc
        m_ref[0, j] = jnp.broadcast_to(m, (Hkv, LANES))
        l_ref[0, j] = jnp.broadcast_to(l, (Hkv, LANES))


def paged_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                    near_k: jax.Array, near_v: jax.Array,
                    walk_pid: jax.Array, walk_live: jax.Array,
                    walk_len: jax.Array, near_live: jax.Array,
                    layer=None, interpret: bool = False):
    """Fused two-tier paged decode attention.

    q: (B, H, hd) single-token queries (GQA: H a multiple of Hkv).
    pool_k/pool_v: (P, page, Hkv, hd) shared far pool (stays in HBM/ANY),
      or the stacked per-layer pools (L, P, page, Hkv, hd) with ``layer``
      the (traced) layer to read — the layer scan of the decode step hands
      the whole pool in, so no per-layer pool slice is ever copied.
    near_k/near_v: (C*page, Hkv, hd) global near buffer (VMEM-resident).
    walk_pid/walk_live: (B, W) int32 — per slot, the pool ids of its mapped,
      non-promoted, live pages (front-packed) and each page's live row
      count (partial-last-page mask); entries past ``walk_len[b]`` unused.
    walk_len: (B,) int32.  near_live: (B, C) int32 — per (slot, near-slot)
      live rows (0 masks the whole page, serving non-tenants and empties).

    Returns (out (B,H,hd) f32 unnormalized, m (B,H) f32, l (B,H) f32).
    """
    B, H, hd = q.shape
    page, Hkv = pool_k.shape[-3:-1]
    stacked = pool_k.ndim == 5
    assert stacked == (layer is not None), "layer goes with stacked pools"
    g = H // Hkv
    n_near = near_k.shape[0] // page
    W = walk_pid.shape[1]
    flat = lambda a: jnp.asarray(a, jnp.int32).reshape(-1)
    # (B, g, Hkv, hd): each GQA group is one (Hkv, hd) panel
    qg = q.reshape(B, Hkv, g, hd).transpose(0, 2, 1, 3)

    kb = max(1, min(W, BLOCK_ROWS // page))      # far pages per block
    kernel = functools.partial(_paged_attention_kernel, page=page,
                               n_near=n_near, W=W, kb=kb, scale=hd ** -0.5,
                               stacked=stacked)
    per_slot = lambda b, *_: (b, 0, 0, 0)
    resident = lambda b, *_: (0, 0, 0)
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, g, Hkv, hd), per_slot),
                pl.BlockSpec((n_near * page, Hkv, hd), resident),
                pl.BlockSpec((n_near * page, Hkv, hd), resident),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, g, Hkv, hd), per_slot),
                pl.BlockSpec((1, g, Hkv, LANES), per_slot),
                pl.BlockSpec((1, g, Hkv, LANES), per_slot),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, kb * page, Hkv, hd), pool_k.dtype),
                pltpu.VMEM((2, kb * page, Hkv, hd), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2, kb)),   # (K|V, buffer, page)
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, g, Hkv, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, g, Hkv, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, g, Hkv, LANES), jnp.float32),
        ],
        name="paged_walk",
        interpret=interpret,
    )(flat(0 if layer is None else layer), flat(walk_pid), flat(walk_live),
      flat(walk_len), flat(near_live), qg, near_k, near_v, pool_k, pool_v)
    heads = lambda a: a.transpose(0, 2, 1, *range(3, a.ndim))
    return (heads(out).reshape(B, H, hd),
            heads(m[..., 0]).reshape(B, H),
            heads(l[..., 0]).reshape(B, H))
