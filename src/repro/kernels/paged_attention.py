"""Pallas flash-decode kernel that walks the page table INSIDE the kernel.

The paged far tier's read path used to materialize every slot's full far
view — a `(B, n_pages*page, Hkv, hd)` gather per decode step per layer —
before attending it, touching `n_pages*page` rows per slot regardless of how
few pages were actually live.  TL-DRAM's far segment is accessed *in place*
through the isolation transistor: cost is paid per access, never per bit of
the segment (PAPER.md §3).  This kernel applies that economics to the
gather path itself:

  grid (B,); per slot the kernel, for ALL KV heads in one grid step,
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
TPU's (8, 128) tiling rule at any head count; the per-slot ints (walk list,
live counts, near live counts) ride in SMEM via scalar prefetch.  Queries
enter as `(B, Hkv, g, hd)`, so KV head h's g queries are one `(g, hd)` panel.
Each block is computed head by head on the MXU: a strided read of head h's
`(rows, hd)` K and V rows from the block buffer (bf16 as 32-bit words,
`_head_pair`), the scores
`q_h · K_hᵀ` as a `(g, rows)` f32 panel with the block's rows on lanes, and
`p · V_h` into a `(g, hd)` f32 accumulator; the softmax state is one
(m, l, acc) row per (head, group) and m/l leave lane-replicated as
`(B, Hkv, g, 128)`.  Operands enter the MXU in their stored dtype with f32
accumulation (bf16 x bf16 products are exact); the `hd ** -0.5` scale is
applied to the f32 scores; f32 operands use HIGHEST; the f32 `p` is never
rounded to bf16 alone (`_p_dot_v`).  No dot spans two heads: an all-heads
dot would do Hkv x the exp work, and its f32 sums would group differently
as Hkv changes, so a KV-head-sharded call would no longer match one device
bit for bit.

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


def _scores(q, k):
    """(g, hd) queries against a block's (rows, hd) keys of one KV head:
    (g, rows) f32 on the MXU, rows on lanes.  bf16 x bf16 products are
    exact in f32; any other pair is taken in f32 at HIGHEST."""
    nt = (((1,), (1,)), ((), ()))
    if q.dtype == k.dtype == jnp.bfloat16:
        return jax.lax.dot_general(q, k, nt,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                               nt, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _p_dot_v(p, v):
    """(g, rows) f32 probabilities times a block's (rows, hd) values of one
    KV head, on the MXU, into f32.  ``p`` is never rounded to bf16 alone:
    bf16 values take p as a hi/lo pair of bf16 panels (two exact passes,
    p_hi + p_lo carries 16 of p's 24 bits); f32 values a HIGHEST dot."""
    if v.dtype == jnp.bfloat16:
        hi = p.astype(jnp.bfloat16)
        lo = (p - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return (jnp.dot(hi, v, preferred_element_type=jnp.float32)
                + jnp.dot(lo, v, preferred_element_type=jnp.float32))
    return jnp.dot(p, v.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _head_pair(ref, start, n, s, keep=None):
    """KV heads 2s and 2s+1: their ``(n, hd)`` bf16 rows ``start`` to
    ``start + n`` of a bf16 ``(N, Hkv, hd)`` VMEM ref.  A row packs two
    heads to a 32-bit word (head 2s in the low half of word s, 2s+1 in the
    high half) and a bf16 panel packs two rows to a word (row 2i low, 2i+1
    high), so each head is two sublane-strided 32-bit loads (the even and
    the odd rows) and a repacking of their halves: exact, with no bf16
    unpacking.  ``keep``: (even, odd) ``(n/2, hd)`` masks of the rows to
    keep; the others read as zero."""
    N, Hkv, hd = ref.shape
    half = Hkv // 2
    words = ref.bitcast(jnp.uint32).reshape(N * half, hd)

    def rows(parity):
        w = words[pl.ds((start + parity) * half + s, n // 2,
                        stride=2 * half), :]
        return w if keep is None else jnp.where(keep[parity], w, 0)

    even, odd = rows(0), rows(1)
    low, high = jnp.uint32(0xFFFF), jnp.uint32(0xFFFF0000)
    return (pltpu.bitcast((even & low) | (odd << 16), jnp.bfloat16),
            pltpu.bitcast((even >> 16) | (odd & high), jnp.bfloat16))


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
    _, Hkv, g, hd = q_ref.shape
    qs = [q_ref[0, h] for h in range(Hkv)]      # (g, hd): head h's queries
    packed = (kbuf.dtype == vbuf.dtype == jnp.bfloat16 and Hkv % 2 == 0
              and page % 2 == 0)                # two heads, two rows a word

    def update(carry, kref, vref, start, lives):
        """One online-softmax update over the ``len(lives)`` pages that
        start at row ``start`` of ``kref``/``vref`` ``(N, Hkv, hd)``, head
        by head: one running-max rescale per block, not per page.  Rows at
        or past their page's ``lives[p]`` are dead and may hold stale or
        uninitialised bytes, so they are kept out of the scores AND zeroed
        in V (0 * NaN is NaN)."""
        rows = len(lives) * page

        def alive(shape, axis):         # the block's live rows along ``axis``
            r = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            end = jnp.zeros(shape, jnp.int32)
            for p, live in enumerate(lives):
                end = jnp.where(r >= p * page, p * page + live, end)
            return r < end

        on_lanes = alive((1, rows), 1)

        def step(h, k, v):
            acc, m, l = carry[h]
            s = jnp.where(on_lanes, _scores(qs[h], k) * scale, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(on_lanes, jnp.exp(s - m_new), 0.0)   # (g, rows)
            return (acc * alpha + _p_dot_v(p, v), m_new,
                    l * alpha + jnp.sum(p, axis=1, keepdims=True))

        new = []
        if packed:
            # rows 2i + parity of the block against their page's live count
            i = jax.lax.broadcasted_iota(jnp.int32, (rows // 2, hd), 0)
            ends = jnp.concatenate([jnp.full((page // 2, hd), live)
                                    for live in lives])
            keep = [2 * (i % (page // 2)) + parity < ends
                    for parity in (0, 1)]
            for s in range(Hkv // 2):
                ks = _head_pair(kref, start, rows, s)
                vs = _head_pair(vref, start, rows, s, keep)
                new += [step(2 * s + j, ks[j], vs[j]) for j in (0, 1)]
            return tuple(new)
        on_sublanes = alive((rows, 1), 0)
        for h in range(Hkv):
            block = (pl.ds(start, rows), h, slice(None))
            new.append(step(h, kref[block],
                            jnp.where(on_sublanes, vref[block], 0)))
        return tuple(new)

    carry = tuple((jnp.zeros((g, hd), jnp.float32),
                   jnp.full((g, 1), NEG_INF, jnp.float32),
                   jnp.zeros((g, 1), jnp.float32)) for _ in range(Hkv))

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
        return update(carry, nk_ref, nv_ref, i * kn * page,
                      [near_live_ref[b * n_near + i * kn + p]
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
        return update(carry, kbuf.at[slot], vbuf.at[slot], 0, lives)

    carry = jax.lax.fori_loop(0, pl.cdiv(n, kb), far_block, carry)

    for h in range(Hkv):
        acc, m, l = carry[h]
        o_ref[0, h] = acc
        m_ref[0, h] = jnp.broadcast_to(m, (g, LANES))
        l_ref[0, h] = jnp.broadcast_to(l, (g, LANES))


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
    # (B, Hkv, g, hd): KV head h's queries are one (g, hd) panel
    qh = q.reshape(B, Hkv, g, hd)

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
                pl.BlockSpec((1, Hkv, g, hd), per_slot),
                pl.BlockSpec((n_near * page, Hkv, hd), resident),
                pl.BlockSpec((n_near * page, Hkv, hd), resident),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, Hkv, g, hd), per_slot),
                pl.BlockSpec((1, Hkv, g, LANES), per_slot),
                pl.BlockSpec((1, Hkv, g, LANES), per_slot),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, kb * page, Hkv, hd), pool_k.dtype),
                pltpu.VMEM((2, kb * page, Hkv, hd), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2, kb)),   # (K|V, buffer, page)
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, g, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, g, LANES), jnp.float32),
        ],
        name="paged_walk",
        interpret=interpret,
    )(flat(0 if layer is None else layer), flat(walk_pid), flat(walk_live),
      flat(walk_len), flat(near_live), qh, near_k, near_v, pool_k, pool_v)
    return (out.reshape(B, H, hd), m[..., 0].reshape(B, H),
            l[..., 0].reshape(B, H))
