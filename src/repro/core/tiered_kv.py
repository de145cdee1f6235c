"""Tiered KV cache: the TL-DRAM near/far substrate applied to decode serving.

Mapping (docs/design.md §2b):

  far tier   : the full KV cache (master copy; new tokens append here) —
               the long-bitline segment.  Gather-addressed => slow path.
  near tier  : a small contiguous buffer of *copies* of hot KV pages —
               the near segment.  Dense, VMEM-streamable by the Pallas
               kernel (`kernels.tiered_attention`) => fast path.
  IST        : promotions/evictions are pure on-device page copies
               (`dynamic_update_slice`) — no collectives, no host round-trip,
               mirroring the paper's channel-free inter-segment transfer
               (asserted by tests: migration HLO contains no collective ops).
  policy     : every `interval` decode steps, a scoring pass measures per-page
               attention mass with the current queries (the paper's
               interval-sampled activation counts), EMA-updates page scores,
               and runs the shared vectorized engine (`repro.tier.jax_engine`)
               under any of the four paper policies — SC, WMC, BBC (default)
               or STATIC (profile preload via `preload_static`).

KV pages are immutable once written, so evictions are always clean (the
paper's dirty-eviction write-back IST never triggers for this workload — a
fact we note rather than hide).

Two far-tier layouts share the policy machinery:

  monolithic : the original per-slot (B, T, Hkv, hd) buffer — every
               sequence owns private copies of its pages (top half of this
               module).
  paged      : a refcounted shared page pool with per-slot page tables and
               a GLOBAL near tier scored by aggregate attention mass
               (docs/design.md §2d; the `paged_*` functions + `PagePool`
               below).  Shared prompt prefixes are stored once and
               promoted once for all tenants — the serving engine's
               default since PR 3, fed by `repro.serve.prefix`.

Correctness invariant (tested): near+far partitioned attention with LSE merge
is *exactly* standard attention over the full cache — in both layouts
(tests/test_read_path.py, tests/test_paged_read_path.py).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.tier import TierCosts, ema_update
from repro.tier.jax_engine import (apply_promotions, plan_promotions,
                                   preload_static)
from repro.kernels import interpret_mode, ops, ref

# Cost model (napkin math, documented in docs/experiments.md): far pages are
# gather-addressed — effective HBM bandwidth for 2KB-grain gathers is ~1/4 of
# streaming bandwidth on TPU-class memory systems; near pages stream at full
# bandwidth.  Migration copies a page (read + write) at streaming bandwidth.
DEFAULT_COSTS = TierCosts(near_cost=1.0, far_cost=4.0, migrate_cost=8.0,
                          hysteresis=2.0, min_score=2.0, decay=0.9)


@dataclass
class TieredKVConfig:
    page: int = 128               # tokens per page
    near_pages: int = 8           # near-tier capacity: pages per sequence
                                  # (monolithic mode) or total pages shared
                                  # by the whole pool (paged mode)
    interval: int = 16            # decode steps between planning passes
    max_promotions: int = 2       # migrations per planning pass
    policy: str = "BBC"           # SC | WMC | BBC | STATIC
    costs: TierCosts = DEFAULT_COSTS
    gather_kernel: bool = False   # paged mode: materialize the far view with
                                  # the Pallas paged-gather kernel instead of
                                  # an XLA take (parity pinned by tests)
    fused_kernel: bool = False    # paged mode: read through the fused
                                  # page-table-walking Pallas kernel
                                  # (kernels.paged_attention) — NO far-view
                                  # materialization; far bytes touched per
                                  # step = live non-promoted page rows only.
                                  # The dense path stays the oracle.
    mesh: object = None           # jax.sharding.Mesh: shard the pool/near
                                  # buffers by KV head over the 'model' axis
                                  # (shard_map around every Pallas read;
                                  # scatter/slice paths partition under
                                  # GSPMD).  Falls back to replication when
                                  # Hkv does not divide the axis
                                  # (sharding.specs.kv_shard_count).  None:
                                  # single-device (the default everywhere).


def init_tiered_cache(k_cache: jax.Array, v_cache: jax.Array,
                      cfg: TieredKVConfig) -> dict:
    """Wrap an existing (B, T, Hkv, hd) far cache with near-tier state."""
    B, T, Hkv, hd = k_cache.shape
    assert T % cfg.page == 0, f"cache length {T} must be a page multiple"
    n_pages = T // cfg.page
    C = cfg.near_pages
    return {
        "far_k": k_cache, "far_v": v_cache,
        "near_k": jnp.zeros((B, C * cfg.page, Hkv, hd), k_cache.dtype),
        "near_v": jnp.zeros((B, C * cfg.page, Hkv, hd), v_cache.dtype),
        "slot_of_page": -jnp.ones((B, n_pages), jnp.int32),
        "page_of_slot": -jnp.ones((B, C), jnp.int32),
        "scores": jnp.zeros((B, n_pages), jnp.float32),
        # SC/WMC LRU stamps: planning-interval index of each page's last
        # nonzero attention mass (BBC/STATIC ignore them).
        "last_use": jnp.zeros((B, n_pages), jnp.float32),
        "step": jnp.zeros((), jnp.int32),
        "migrations": jnp.zeros((), jnp.int32),
    }


def _pos_vec(pos, B: int) -> jax.Array:
    """Normalize a decode position to a per-sequence (B,) vector.

    Every read-path entry point accepts either the legacy scalar (one
    position shared by the whole batch) or a ragged per-slot vector (the
    continuous-batching serving engine's slot pool)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    return pos


def near_token_count(cache: dict, cfg: TieredKVConfig) -> jax.Array:
    """(B,) live near-tier token count.  Occupied slots always form a
    prefix (pinned by tests/test_read_path.py), so count * page is the
    exact live region the kernel streams."""
    occupied = (cache["page_of_slot"] >= 0)
    return occupied.sum(axis=1).astype(jnp.int32) * cfg.page


def reset_sequences(cache: dict, rows: jax.Array) -> dict:
    """Clear tier state for retired slots (rows: (B,) bool mask).

    The far/near K,V buffers are left untouched — a cleared mapping makes
    the near copies unreachable (near_len excludes them) and the next
    prefill overwrites the far rows; only the policy state must not leak
    into the slot's next tenant."""
    cache = dict(cache)
    r = rows[:, None]
    cache["slot_of_page"] = jnp.where(r, -1, cache["slot_of_page"])
    cache["page_of_slot"] = jnp.where(r, -1, cache["page_of_slot"])
    cache["scores"] = jnp.where(r, 0.0, cache["scores"])
    cache["last_use"] = jnp.where(r, 0.0, cache["last_use"])
    return cache


def append_token(cache: dict, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array) -> dict:
    """Append one token's K/V to the far tier (master copy).

    pos: scalar position, or a (B,) vector for ragged per-slot appends."""
    cache = dict(cache)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        b_idx = jnp.arange(k_new.shape[0])
        cache["far_k"] = cache["far_k"].at[b_idx, pos].set(k_new[:, 0])
        cache["far_v"] = cache["far_v"].at[b_idx, pos].set(v_new[:, 0])
    else:
        cache["far_k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["far_k"], k_new, pos, 1)
        cache["far_v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["far_v"], v_new, pos, 1)
    return cache


def tiered_attention(cache: dict, q: jax.Array, pos: jax.Array,
                     cfg: TieredKVConfig) -> jax.Array:
    """Two-tier decode attention.  q: (B,H,hd); pos: scalar current
    position, or a (B,) vector of ragged per-slot positions.

    Near path: Pallas kernel over the contiguous near buffer.
    Far path: XLA attention over the far cache, with promoted pages masked
    out (they are served from the near tier) and positions >= pos masked.
    """
    B, H, hd = q.shape
    T = cache["far_k"].shape[1]
    page = cfg.page
    pos = _pos_vec(pos, B)

    # Near tier: occupied slots always form a prefix (promotions fill empty
    # slots in index order and evictions replace in place), so the live
    # region is simply count * page.
    near_len = near_token_count(cache, cfg)

    out_n, m_n, l_n = _near_stats(q, cache, near_len, cfg)

    # far mask: slot < pos and the slot's page is not promoted
    slots = jnp.arange(T)
    page_of_slot_idx = slots // page                        # (T,)
    promoted = cache["slot_of_page"][:, page_of_slot_idx] >= 0   # (B,T)
    live = (slots[None, :] < pos[:, None]) & ~promoted
    out_f, m_f, l_f = _far_stats(q, cache["far_k"], cache["far_v"], live)

    return ref.merge_attention_stats([(out_n, m_n, l_n), (out_f, m_f, l_f)])


def _near_stats(q, cache, near_len, cfg: TieredKVConfig):
    from repro.kernels.tiered_attention import near_decode_attention
    return near_decode_attention(q, cache["near_k"], cache["near_v"],
                                 near_len, interpret=interpret_mode())


def _far_stats(q, k, v, live_mask):
    """XLA far-tier attention returning online-softmax stats.
    q: (B,H,hd); k/v: (B,T,Hkv,hd); live_mask: (B,T) bool."""
    B, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qh = q.reshape(B, Hkv, g, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qh, k,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    s = jnp.where(live_mask[:, None, None, :], s, ref.NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None]) * live_mask[:, None, None, :]
    l = p.sum(axis=-1)
    # f32 p@v accumulation, matching the Pallas kernels and the dense
    # decode path — cross-path noise stays at reduction-order level
    out = jnp.einsum("bkgt,btkd->bkgd", p, v,
                     preferred_element_type=jnp.float32)
    return (out.reshape(B, H, hd),
            m.reshape(B, H), l.reshape(B, H))


def _token_masses(q: jax.Array, k: jax.Array, live: jax.Array) -> jax.Array:
    """(B, T) per-token attention mass, summed over heads (the caller
    divides by H after its page-sum).  live: (B, T) bool; dead tokens get
    exactly zero mass.  Shared by the monolithic and paged scoring passes
    so both modes stay decision-identical by construction."""
    B, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qh = q.reshape(B, Hkv, g, hd) * hd ** -0.5
    s = jnp.einsum("bkgd,btkd->bkgt", qh, k).astype(jnp.float32)
    lv = live[:, None, None, :]
    s = jnp.where(lv, s, ref.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(lv, p, 0.0)
    return p.sum(axis=(1, 2))                                # (B,T)


def page_masses(q: jax.Array, cache: dict, pos: jax.Array,
                cfg: TieredKVConfig) -> jax.Array:
    """Scoring pass: per-page attention mass with the current queries —
    the interval-sampled activation counts of the paper's BBC.

    Returns (B, n_pages) f32 normalized masses over the *whole* cache
    (near-resident pages included, so retention scores stay fresh).
    ``pos`` may be a scalar or a ragged (B,) vector."""
    B, H, _ = q.shape
    k = cache["far_k"]
    T = k.shape[1]
    live = jnp.arange(T)[None, :] < _pos_vec(pos, B)[:, None]
    mass = _token_masses(q, k, live)
    n_pages = T // cfg.page
    return mass.reshape(B, n_pages, cfg.page).sum(-1) / max(H, 1)


def _copy_pool_pages(near_k, near_v, pool_k, pool_v, pages, slots, valid,
                     page: int):
    """IST analogue: copy up to K pages of a (P, page, ...) page array into
    (C*page, ...) near buffers (pure on-device dynamic slices; invalid plan
    entries are dropped).  Serves both tier layouts — the monolithic far
    buffer reshapes to page-major via ``_copy_pages``."""

    def copy_page(i, bufs):
        nk, nv = bufs
        src = jnp.where(valid[i], pages[i], 0)
        dst = jnp.where(valid[i], slots[i], 0) * page
        page_k = jax.lax.dynamic_slice_in_dim(pool_k, src, 1, 0)[0]
        page_v = jax.lax.dynamic_slice_in_dim(pool_v, src, 1, 0)[0]
        nk_new = jax.lax.dynamic_update_slice_in_dim(nk, page_k, dst, 0)
        nv_new = jax.lax.dynamic_update_slice_in_dim(nv, page_v, dst, 0)
        nk = jnp.where(valid[i], nk_new, nk)
        nv = jnp.where(valid[i], nv_new, nv)
        return nk, nv

    return jax.lax.fori_loop(0, pages.shape[0], copy_page, (near_k, near_v))


def _copy_pages(near_k, near_v, far_k, far_v, rows, slots, valid, page: int):
    """Monolithic-layout wrapper: view the (T, ...) far buffer page-major
    and defer to the shared page copier."""
    return _copy_pool_pages(
        near_k, near_v,
        far_k.reshape(far_k.shape[0] // page, page, *far_k.shape[1:]),
        far_v.reshape(far_v.shape[0] // page, page, *far_v.shape[1:]),
        rows, slots, valid, page)


def plan_and_migrate(cache: dict, q: jax.Array, pos: jax.Array,
                     cfg: TieredKVConfig, idle=True,
                     masses: jax.Array | None = None) -> dict:
    """One planning interval: score -> plan -> migrate (vectorized over
    batch) under ``cfg.policy``.

    Only pages that are completely written (page_end <= pos) are candidates.
    Migration is a pure on-device copy — the IST analogue.  ``idle`` is the
    WMC gate: pass False (or a traced bool) when the serving step has no
    spare migration budget; SC/BBC ignore it, STATIC never migrates.
    ``pos`` may be a scalar or a ragged (B,) vector (the serving engine's
    slot pool — each slot's complete-page frontier is its own).
    ``masses``: optionally pass a precomputed ``page_masses(q, ...)`` result
    (callers that also need the masses for metrics avoid scoring twice).
    """
    if cfg.policy.upper() == "STATIC":
        return cache   # OS-exposed mechanism: no runtime migration, and no
                       # point paying the scoring pass for dead state
    cache = dict(cache)
    if masses is None:
        masses = page_masses(q, cache, pos, cfg)
    n_pages = masses.shape[1]
    pos_b = _pos_vec(pos, masses.shape[0])
    complete = (jnp.arange(n_pages)[None, :] + 1) * cfg.page <= pos_b[:, None]
    masses = jnp.where(complete, masses, 0.0)
    # EMA in "activations per interval" units: scale mass to a count-like
    # magnitude so TierCosts thresholds behave like the DRAM policy's.
    acts = masses * cfg.interval
    cache["scores"] = ema_update(cache["scores"], acts, cfg.costs)
    cache["last_use"] = jnp.where(acts > 0, cache["step"].astype(jnp.float32),
                                  cache["last_use"])
    cache["step"] = cache["step"] + 1

    # SC/WMC cache what received attention mass *this interval*; BBC keeps
    # its sustained-reuse eligibility over the full EMA score population.
    sc_like = cfg.policy.upper() in ("SC", "WMC")

    def per_seq(acts_row, scores, last_use, slot_of_page, page_of_slot,
                near_k, near_v, far_k, far_v):
        rows, slots, valid = plan_promotions(
            scores, slot_of_page, page_of_slot, cfg.costs,
            cfg.max_promotions, policy=cfg.policy, last_use=last_use,
            accessed=(acts_row > 0) if sc_like else None, idle=idle)
        slot_of_page, page_of_slot = apply_promotions(
            slot_of_page, page_of_slot, rows, slots, valid)
        near_k, near_v = _copy_pages(near_k, near_v, far_k, far_v, rows,
                                     slots, valid, cfg.page)
        return slot_of_page, page_of_slot, near_k, near_v, valid.sum()

    (cache["slot_of_page"], cache["page_of_slot"], cache["near_k"],
     cache["near_v"], n_migr) = jax.vmap(per_seq)(
        acts, cache["scores"], cache["last_use"], cache["slot_of_page"],
        cache["page_of_slot"], cache["near_k"], cache["near_v"],
        cache["far_k"], cache["far_v"])
    cache["migrations"] = cache["migrations"] + n_migr.sum().astype(jnp.int32)
    return cache


def preload_static_kv(cache: dict, profile_masses: jax.Array,
                      pos: jax.Array, cfg: TieredKVConfig,
                      row_mask: jax.Array | None = None) -> dict:
    """OS-exposed static placement: fill the near tier with the profile's
    hottest pages per sequence (the paper's t=0 profiling step), copying the
    pages in — then serve with ``policy="STATIC"`` (no runtime migration).

    profile_masses: (B, n_pages) profiled per-page attention mass.
    pos: current decode position (scalar or ragged (B,) vector) — only
    completely-written pages (page_end <= pos) may be pinned, else the near
    copy would contain unwritten positions that ``tiered_attention`` masks
    out of the far pass (the same guard ``plan_and_migrate`` applies).
    row_mask: optional (B,) bool — only pin these sequences, leaving the
    others' placements untouched (the serving engine pins each slot once,
    at its first planning interval after admission)."""
    cache = dict(cache)
    C = cache["page_of_slot"].shape[1]
    B, n_pages = profile_masses.shape
    pos_b = _pos_vec(pos, B)
    complete = (jnp.arange(n_pages)[None, :] + 1) * cfg.page <= pos_b[:, None]
    profile_masses = jnp.where(complete, profile_masses, 0.0)

    def per_seq(masses, near_k, near_v, far_k, far_v):
        slot_of_page, page_of_slot = preload_static(masses, C)
        slots = jnp.arange(C, dtype=jnp.int32)
        valid = page_of_slot >= 0
        rows = jnp.maximum(page_of_slot, 0)
        near_k, near_v = _copy_pages(near_k, near_v, far_k, far_v, rows,
                                     slots, valid, cfg.page)
        return slot_of_page, page_of_slot, near_k, near_v

    new_sop, new_pos_, new_nk, new_nv = jax.vmap(per_seq)(
        profile_masses, cache["near_k"], cache["near_v"], cache["far_k"],
        cache["far_v"])
    if row_mask is None:
        cache["slot_of_page"], cache["page_of_slot"] = new_sop, new_pos_
        cache["near_k"], cache["near_v"] = new_nk, new_nv
    else:
        r = row_mask
        cache["slot_of_page"] = jnp.where(r[:, None], new_sop,
                                          cache["slot_of_page"])
        cache["page_of_slot"] = jnp.where(r[:, None], new_pos_,
                                          cache["page_of_slot"])
        r4 = r[:, None, None, None]
        cache["near_k"] = jnp.where(r4, new_nk, cache["near_k"])
        cache["near_v"] = jnp.where(r4, new_nv, cache["near_v"])
    return cache


# ===========================================================================
# Paged far tier: a refcounted shared page pool (docs/design.md §2d)
#
# The dense per-slot (B, T, Hkv, hd) far buffer above gives every sequence a
# private copy of every KV page.  The paged mode below restructures the far
# tier into one *pool* of pages shared by all slots:
#
#   pool_k/pool_v : (P, page, Hkv, hd)  — the master copies
#   page_table    : (B, n_pages) int32 — pool page id per slot page, -1 if
#                                        unmapped (per-slot indirection)
#   PagePool      : host-side refcounted allocator (free list + prefix-cache
#                                        retention flags)
#
# Sequences admitted with a shared prompt prefix map the *same* pool pages
# (refcount++) instead of re-storing them, and the near tier becomes global:
# one (C*page,) buffer whose pages are scored by the AGGREGATE attention
# mass of every referencing sequence and promoted once for all tenants —
# the paper's one-IST-many-accesses economics made literal.  Page-table
# dead-entry handling follows the shared-engine sentinel idiom: -1 entries
# route through clamped gathers and are masked from every read.
#
# Since ISSUE 5 the pool is the serving stack's SINGLE SOURCE OF TRUTH
# (docs/design.md §2f): prefill and decode write pool pages directly, no
# dense per-slot master exists, and the near buffers are derived copies
# re-gathered from the pool on mapping changes (`refresh_near_from_pool`).
# The functions below therefore accept either the buffer-carrying dict of
# `init_paged_cache` (the single-layer model object the fuzz suite drives)
# or the mapping-only `init_tier_state` dict plus explicit buffers.
# ===========================================================================


class PagePool:
    """Host-side refcounted allocator over a fixed pool of KV pages.

    ``refcount[p]`` counts the *slots* whose page table references page p —
    the invariant the paged fuzz suite pins.  ``cached[p]`` marks pages
    additionally retained by the radix prefix index (``repro.serve.prefix``)
    after their refcount drops to zero; they stay allocated (re-admissions
    hit them) until the index evicts them under pool pressure.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self.refcount = np.zeros(n_pages, np.int32)
        self.cached = np.zeros(n_pages, bool)
        self._free = deque(range(n_pages))

    def available(self) -> int:
        return len(self._free)

    def allocate(self, n: int) -> list[int]:
        """Take n free pages (refcount 1: the mapping slot holds the ref)."""
        if len(self._free) < n:
            raise RuntimeError(
                f"page pool exhausted: want {n}, free {len(self._free)}")
        out = [self._free.popleft() for _ in range(n)]
        self.refcount[out] = 1
        return out

    def acquire(self, pages) -> None:
        """Another slot references already-allocated pages (prefix hit)."""
        for p in pages:
            assert self.refcount[p] > 0 or self.cached[p], \
                f"acquire of unallocated page {p}"
            self.refcount[p] += 1

    def release(self, pages) -> list[int]:
        """Drop one slot reference per page; returns pages actually freed
        (refcount hit zero and the prefix index does not retain them)."""
        freed = []
        for p in pages:
            if p < 0:
                continue
            assert self.refcount[p] > 0, f"release of free page {p}"
            self.refcount[p] -= 1
            if self.refcount[p] == 0 and not self.cached[p]:
                self._free.append(p)
                freed.append(p)
        return freed

    def retain(self, pages) -> None:
        """Prefix-index retention: keep pages allocated at refcount zero."""
        for p in pages:
            self.cached[p] = True

    def drop_cached(self, pages) -> list[int]:
        """Prefix-index eviction; returns pages freed to the pool."""
        freed = []
        for p in pages:
            self.cached[p] = False
            if self.refcount[p] == 0:
                self._free.append(p)
                freed.append(p)
        return freed


def init_tier_state(n_slots: int, n_pages: int, pool_pages: int,
                    near_pages: int) -> dict:
    """Mapping-only paged tier state: page tables, the global near mapping
    and the policy scores — WITHOUT pool or near buffers.  The serving
    engine owns those separately (per layer) since the ownership inversion
    (ISSUE 5); every ``paged_*`` function accepts this dict plus explicit
    buffers, or the full buffer-carrying dict of ``init_paged_cache`` (the
    single-layer model object the fuzz suite drives)."""
    return {
        "page_table": -jnp.ones((n_slots, n_pages), jnp.int32),
        "slot_of_page": -jnp.ones((pool_pages,), jnp.int32),
        "page_of_slot": -jnp.ones((near_pages,), jnp.int32),
        "scores": jnp.zeros((pool_pages,), jnp.float32),
        "last_use": jnp.zeros((pool_pages,), jnp.float32),
        "step": jnp.zeros((), jnp.int32),
        "migrations": jnp.zeros((), jnp.int32),
    }


def init_paged_cache(cfg: TieredKVConfig, n_slots: int, n_pages: int,
                     pool_pages: int, n_kv_heads: int, head_dim: int,
                     dtype=jnp.bfloat16) -> dict:
    """Device state for the paged far tier + global near tier."""
    C = cfg.near_pages
    return {
        **init_tier_state(n_slots, n_pages, pool_pages, C),
        "pool_k": jnp.zeros((pool_pages, cfg.page, n_kv_heads, head_dim),
                            dtype),
        "pool_v": jnp.zeros((pool_pages, cfg.page, n_kv_heads, head_dim),
                            dtype),
        "near_k": jnp.zeros((C * cfg.page, n_kv_heads, head_dim), dtype),
        "near_v": jnp.zeros((C * cfg.page, n_kv_heads, head_dim), dtype),
    }


def paged_append_token(cache: dict, k_new: jax.Array, v_new: jax.Array,
                       pos: jax.Array, cfg: TieredKVConfig) -> dict:
    """Append one token's K/V through the page table into the pool.

    k_new/v_new: (B, 1, Hkv, hd); pos: (B,) per-slot positions.  Writes to
    unmapped pages — and to positions at/past the cache capacity, whose
    page index would otherwise clamp onto the LAST page and corrupt it —
    are dropped (out-of-bounds sentinel)."""
    cache = dict(cache)
    pos = _pos_vec(pos, k_new.shape[0])
    P = cache["pool_k"].shape[0]
    n_pages = cache["page_table"].shape[1]
    j = pos // cfg.page
    pid = jnp.take_along_axis(cache["page_table"], j[:, None], axis=1)[:, 0]
    safe = jnp.where((pid >= 0) & (j < n_pages), pid, P)
    off = pos % cfg.page
    cache["pool_k"] = cache["pool_k"].at[safe, off].set(k_new[:, 0],
                                                        mode="drop")
    cache["pool_v"] = cache["pool_v"].at[safe, off].set(v_new[:, 0],
                                                        mode="drop")
    return cache


def paged_far_view(cache: dict, cfg: TieredKVConfig):
    """Materialize each slot's far cache from the pool via its page table.

    Returns (far_k, far_v) of shape (B, n_pages*page, Hkv, hd); unmapped
    pages come out as page 0's content and MUST be masked by the caller
    (every caller masks on ``page_table >= 0``)."""
    pt = cache["page_table"]
    B, n_pages = pt.shape
    if cfg.gather_kernel:
        # the kernel gets the RAW table: its -1 => zeros contract is live
        # (the XLA path below clamps instead — either way, unmapped content
        # is arbitrary and masked)
        from repro.kernels.paged_gather import paged_gather
        far_k = paged_gather(cache["pool_k"], pt, interpret=interpret_mode(),
                             mesh=cfg.mesh)
        far_v = paged_gather(cache["pool_v"], pt, interpret=interpret_mode(),
                             mesh=cfg.mesh)
        return far_k, far_v
    safe = jnp.maximum(pt, 0)
    _, page, Hkv, hd = cache["pool_k"].shape
    far_k = cache["pool_k"][safe].reshape(B, n_pages * page, Hkv, hd)
    far_v = cache["pool_v"][safe].reshape(B, n_pages * page, Hkv, hd)
    return far_k, far_v


def _front_pack_walk(visit: jax.Array, arrays: dict) -> dict:
    """Front-pack per-slot page walks in page order: for each (B, n_pages)
    array in ``arrays``, keep entries where ``visit`` holds, packed to the
    front (stable — non-visited entries key past the end and come out as
    the array's masked-fill value).  Shared by the READ walk
    (``paged_step_metadata``: mapped & ~promoted & live) and the SCORE walk
    (``paged_score_walk``: mapped & live) so the packing contract the
    kernels rely on cannot desynchronize between them.  Adds ``"len"``:
    (B,) i32 visited count."""
    B, n_pages = visit.shape
    j = jnp.arange(n_pages)
    order = jnp.argsort(jnp.where(visit, j[None, :], n_pages), axis=1)
    out = {k: jnp.take_along_axis(v, order, axis=1).astype(jnp.int32)
           for k, v in arrays.items()}
    out["len"] = visit.sum(axis=1).astype(jnp.int32)
    return out


def paged_step_metadata(cache: dict, lengths: jax.Array,
                        cfg: TieredKVConfig,
                        append_pos: jax.Array | None = None,
                        pool_pages: int | None = None) -> dict:
    """Per-decode-step read-path metadata — small int arrays computed ONCE
    per step from ``(page_table, slot_of_page, page_of_slot, lengths)`` and
    shared by every layer's read (fused kernel inputs AND the dense oracle's
    masks).  Nothing ``(B, n_pages, C)``-shaped is built here or downstream
    (pinned by tests/test_fused_serving.py).

    lengths: (B,) live token count per slot (callers reading "tokens < pos"
    pass ``pos``; the serving decode step passes ``pos + 1`` so the token
    appended this step is attended, matching ``decode_attention``).

    Returns:
      walk_pid  (B, n_pages) i32 : pool ids of the slot's mapped,
                                   NON-promoted, live pages, front-packed in
                                   page order; entries past walk_len unused
      walk_live (B, n_pages) i32 : live rows of each walked page (the
                                   partial-last-page mask, 1..page)
      walk_len  (B,) i32         : number of far pages to walk
      j_of      (B, C) i32       : slot-page index resident in near slot c
                                   for this sequence (-1: not a tenant)
      near_live (B, C) i32       : live rows this sequence reads from near
                                   slot c (0 masks the panel)
      mapped / promoted (B, n_pages) bool : the underlying page states
      pt        (B, n_pages) i32  : the raw page table (the dense
                                   pool-native read path materializes its
                                   per-layer far view from it)
      append_pid/append_off (B,) i32 (only with ``append_pos``): the pool
        page + in-page offset the step's new token writes through the page
        table (sentinel P for unmapped/out-of-range — ``mode="drop"``).

    ``cache`` may be a mapping-only tier-state dict (no pool buffers) —
    pass ``pool_pages`` explicitly then (the serving engine owns the pool
    buffers separately since the ownership inversion, ISSUE 5).
    """
    pt = cache["page_table"]
    B, n_pages = pt.shape
    page = cfg.page
    P = cache["pool_k"].shape[0] if "pool_k" in cache else pool_pages
    assert P is not None, "need pool_k in cache or an explicit pool_pages"
    C = cache["page_of_slot"].shape[0]
    lengths = _pos_vec(lengths, B)

    mapped = pt >= 0
    sop_of_page = cache["slot_of_page"][jnp.maximum(pt, 0)]       # (B,n_pages)
    promoted = mapped & (sop_of_page >= 0)
    j = jnp.arange(n_pages)
    page_live = jnp.clip(lengths[:, None] - j[None, :] * page, 0, page)
    visit = mapped & ~promoted & (page_live > 0)
    walk = _front_pack_walk(visit, {"pid": jnp.where(visit, pt, 0),
                                    "live": jnp.where(visit, page_live, 0)})
    walk_pid, walk_live, walk_len = walk["pid"], walk["live"], walk["len"]

    # near tenancy by SCATTER (j_of[b, near_slot_of(b,j)] = j), not by the
    # (B, n_pages, C) equality tensor the per-layer path used to rebuild
    near_slot = jnp.where(promoted, sop_of_page, C)               # (B,n_pages)
    j_of = jnp.full((B, C), -1, jnp.int32).at[
        jnp.arange(B)[:, None], near_slot].set(
            jnp.broadcast_to(j[None, :], (B, n_pages)).astype(jnp.int32),
            mode="drop")
    near_live = jnp.where(
        j_of >= 0, jnp.clip(lengths[:, None] - j_of * page, 0, page), 0)

    meta = {"walk_pid": walk_pid.astype(jnp.int32),
            "walk_live": walk_live.astype(jnp.int32),
            "walk_len": walk_len,
            "j_of": j_of, "near_live": near_live.astype(jnp.int32),
            "mapped": mapped, "promoted": promoted,
            "pt": pt.astype(jnp.int32)}
    if append_pos is not None:
        append_pos = _pos_vec(append_pos, B)
        ja = append_pos // page
        pid = jnp.take_along_axis(pt, jnp.minimum(ja, n_pages - 1)[:, None],
                                  axis=1)[:, 0]
        meta["append_pid"] = jnp.where((pid >= 0) & (ja < n_pages), pid, P)
        meta["append_off"] = append_pos % page
    return meta


def _paged_masks(cache: dict, pos: jax.Array, cfg: TieredKVConfig,
                 meta: dict | None = None):
    """(far_live, near_live) boolean masks for the DENSE paged read path,
    derived from the hoisted per-step metadata.

    far_live (B, T): token is mapped, before the slot's position, and its
    page is NOT near-resident.  near_live (B, C*page): the near slot holds a
    page of this sequence and the token is before the slot's position (the
    global near tier serves every tenant of a promoted page)."""
    B = cache["page_table"].shape[0]
    page = cfg.page
    pos = _pos_vec(pos, B)
    if meta is None:
        meta = paged_step_metadata(cache, pos, cfg)
    T = cache["page_table"].shape[1] * page
    tok = jnp.arange(T)
    far_live = ((tok[None, :] < pos[:, None])
                & jnp.repeat(meta["mapped"] & ~meta["promoted"], page,
                             axis=1))
    near_live = (jnp.arange(page)[None, None, :]
                 < meta["near_live"][:, :, None])
    return far_live, near_live.reshape(B, -1)


def paged_tiered_attention(cache: dict, q: jax.Array, pos: jax.Array,
                           cfg: TieredKVConfig,
                           meta: dict | None = None) -> jax.Array:
    """Two-tier decode attention over the paged far pool + global near tier.

    Exactly standard attention over each slot's live prefix: pages resident
    in the (shared) near buffer are served there for *every* referencing
    sequence and masked out of the far pass; the LSE merge is exact.

    ``cfg.fused_kernel``: read through the page-table-walking Pallas kernel
    (`kernels.paged_attention`) — no far-view materialization; only the
    slot's live, non-promoted pages transit VMEM.  Default: the dense XLA
    path (the oracle the kernel is validated against).  ``meta``: optionally
    pass a precomputed ``paged_step_metadata`` (the serving engine computes
    it once per step and shares it across layers)."""
    B = q.shape[0]
    if meta is None:
        meta = paged_step_metadata(cache, pos, cfg)
    if cfg.fused_kernel:
        from repro.kernels.paged_attention import paged_attention_stats
        stats = paged_attention_stats(
            q, cache["pool_k"], cache["pool_v"],
            cache["near_k"], cache["near_v"], meta, mesh=cfg.mesh)
        return ref.merge_attention_stats([stats])
    far_k, far_v = paged_far_view(cache, cfg)
    far_live, near_live = _paged_masks(cache, pos, cfg, meta=meta)
    nk = jnp.broadcast_to(cache["near_k"][None],
                          (B,) + cache["near_k"].shape)
    nv = jnp.broadcast_to(cache["near_v"][None],
                          (B,) + cache["near_v"].shape)
    stats_n = _far_stats(q, nk, nv, near_live)
    stats_f = _far_stats(q, far_k, far_v, far_live)
    return ref.merge_attention_stats([stats_n, stats_f])


def paged_score_walk(cache: dict, pos: jax.Array,
                     cfg: TieredKVConfig) -> dict:
    """SCORE walk list: every mapped page with live rows, front-packed in
    page order — near-resident pages INCLUDED (retention scores must stay
    fresh), which is what distinguishes it from the read walk
    (``paged_step_metadata``, which skips promoted pages).

    Returns score_pid/score_live/score_j (B, n_pages) i32 and score_len
    (B,) i32; ``score_j`` is each entry's slot-page index (sentinel
    n_pages past score_len) so callers can scatter per-entry masses back
    to (B, n_pages) positions."""
    pt = cache["page_table"]
    B, n_pages = pt.shape
    page = cfg.page
    pos_b = _pos_vec(pos, B)
    j = jnp.arange(n_pages)
    page_live = jnp.clip(pos_b[:, None] - j[None, :] * page, 0, page)
    visit = (pt >= 0) & (page_live > 0)
    walk = _front_pack_walk(
        visit, {"pid": jnp.where(visit, pt, 0),
                "live": jnp.where(visit, page_live, 0),
                "j": jnp.where(visit, jnp.broadcast_to(j[None, :],
                                                       (B, n_pages)),
                               n_pages)})
    return {"score_pid": walk["pid"], "score_live": walk["live"],
            "score_j": walk["j"], "score_len": walk["len"]}


def paged_page_masses(q: jax.Array, cache: dict, pos: jax.Array,
                      cfg: TieredKVConfig) -> jax.Array:
    """Per-slot per-page attention mass over the paged far pool.

    Returns (B, n_pages) f32 — near-resident pages included (scores stay
    fresh), unmapped pages zero.  The *aggregate* pool-page mass that drives
    planning is derived by ``aggregate_pool_masses``.

    ``cfg.fused_kernel``: score through the pool-native page-mass reduction
    kernel (`kernels.paged_masses`) — walks the page table like the fused
    read, touching only live mapped K pages, with NO far-view
    materialization.  Default: the XLA materializing path (the oracle)."""
    B, H, _ = q.shape
    pt = cache["page_table"]
    n_pages = pt.shape[1]
    page = cfg.page
    if cfg.fused_kernel:
        from repro.kernels.paged_masses import paged_masses
        walk = paged_score_walk(cache, pos, cfg)
        mass = paged_masses(q, cache["pool_k"], walk["score_pid"],
                            walk["score_live"], walk["score_len"],
                            interpret=interpret_mode(), mesh=cfg.mesh)
        out = jnp.zeros((B, n_pages), jnp.float32).at[
            jnp.arange(B)[:, None], walk["score_j"]].add(mass, mode="drop")
        return out / max(H, 1)
    far_k, _ = paged_far_view(cache, cfg)
    T = far_k.shape[1]
    live = ((jnp.arange(T)[None, :] < _pos_vec(pos, B)[:, None])
            & jnp.repeat(pt >= 0, page, axis=1))
    mass = _token_masses(q, far_k, live)
    return mass.reshape(B, n_pages, page).sum(-1) / max(H, 1)


def aggregate_pool_masses(cache: dict, masses: jax.Array, pos: jax.Array,
                          cfg: TieredKVConfig) -> jax.Array:
    """Scatter per-slot page masses onto pool pages: a shared page is scored
    by the SUM of every referencing sequence's attention mass on it.  Only
    completely-written pages contribute (the same promotion guard the
    monolithic path applies — a partial page must not enter the near tier).
    """
    pt = cache["page_table"]
    B, n_pages = pt.shape
    P = cache["pool_k"].shape[0]
    pos_b = _pos_vec(pos, B)
    complete = (jnp.arange(n_pages)[None, :] + 1) * cfg.page \
        <= pos_b[:, None]
    m = jnp.where(complete & (pt >= 0), masses, 0.0)
    pid = jnp.where(pt >= 0, pt, P)
    return jnp.zeros((P,), jnp.float32).at[pid.ravel()].add(
        m.ravel(), mode="drop")


def paged_plan_and_migrate(cache: dict, q: jax.Array, pos: jax.Array,
                           cfg: TieredKVConfig, idle=True,
                           masses: jax.Array | None = None) -> dict:
    """One planning interval over the POOL page population (jittable).

    The shared vectorized engine (`repro.tier.jax_engine`) runs once over
    all P pool pages with the global (C,) near mapping — a hot page shared
    by many sequences aggregates their attention mass and is promoted once
    for all of them.  ``masses``: optionally pass a precomputed
    ``paged_page_masses`` result."""
    if cfg.policy.upper() == "STATIC":
        return cache          # per-slot pinning is the engine's host path
    cache = dict(cache)
    if masses is None:
        masses = paged_page_masses(q, cache, pos, cfg)
    acts = aggregate_pool_masses(cache, masses, pos, cfg) * cfg.interval
    cache["scores"] = ema_update(cache["scores"], acts, cfg.costs)
    cache["last_use"] = jnp.where(acts > 0, cache["step"].astype(jnp.float32),
                                  cache["last_use"])
    cache["step"] = cache["step"] + 1
    sc_like = cfg.policy.upper() in ("SC", "WMC")
    pages, slots, valid = plan_promotions(
        cache["scores"], cache["slot_of_page"], cache["page_of_slot"],
        cfg.costs, cfg.max_promotions, policy=cfg.policy,
        last_use=cache["last_use"],
        accessed=(acts > 0) if sc_like else None, idle=idle)
    cache["slot_of_page"], cache["page_of_slot"] = apply_promotions(
        cache["slot_of_page"], cache["page_of_slot"], pages, slots, valid)
    cache["near_k"], cache["near_v"] = _copy_pool_pages(
        cache["near_k"], cache["near_v"], cache["pool_k"], cache["pool_v"],
        pages, slots, valid, cfg.page)
    cache["migrations"] = cache["migrations"] + valid.sum().astype(jnp.int32)
    return cache


def paged_pin_pages(cache: dict, pages, slots, cfg: TieredKVConfig) -> dict:
    """STATIC placement on the pool: map the given pool pages into the given
    (free) near slots and copy their contents in.  ``pages``/``slots`` are
    host lists — the engine's per-slot first-interval pinning pass.

    A mapping-only tier-state dict (no pool/near buffers) updates just the
    mapping; the caller re-derives its near buffers from the pool
    (``refresh_near_from_pool``) — the pool-native engine path."""
    if not len(pages):
        return cache
    cache = dict(cache)
    pages_a = jnp.asarray(list(pages), jnp.int32)
    slots_a = jnp.asarray(list(slots), jnp.int32)
    valid = jnp.ones((len(pages),), bool)
    cache["slot_of_page"] = cache["slot_of_page"].at[pages_a].set(slots_a)
    cache["page_of_slot"] = cache["page_of_slot"].at[slots_a].set(pages_a)
    if "pool_k" in cache:
        cache["near_k"], cache["near_v"] = _copy_pool_pages(
            cache["near_k"], cache["near_v"], cache["pool_k"],
            cache["pool_v"], pages_a, slots_a, valid, cfg.page)
    return cache


def paged_release_pages(cache: dict, pages, cfg: TieredKVConfig) -> dict:
    """Reset tier state for pool pages leaving allocation (freed at retire
    or evicted from the prefix index): zero their scores, and demote any
    near-resident ones — compacting the near mapping so occupied near slots
    remain a prefix (the invariant every read depends on).

    Host-side (numpy mapping surgery + one device reorder of the near
    buffers); runs at admission/retirement boundaries, never per step.  A
    mapping-only tier-state dict (no near buffers) gets the surgery alone;
    the caller re-derives its near buffers from the pool
    (``refresh_near_from_pool``) — the pool-native engine path."""
    pages = [int(p) for p in pages]
    if not pages:
        return cache
    cache = dict(cache)
    P = cache["scores"].shape[0]
    C = cache["page_of_slot"].shape[0]
    page = cfg.page
    scores = np.array(cache["scores"])
    last_use = np.array(cache["last_use"])
    sop = np.array(cache["slot_of_page"])
    ros = np.array(cache["page_of_slot"])
    scores[pages] = 0.0
    last_use[pages] = 0.0
    drop_slots = {int(sop[p]) for p in pages if sop[p] >= 0}
    if drop_slots:
        keep = [c for c in range(C) if ros[c] >= 0 and c not in drop_slots]
        perm = np.arange(C)
        new_ros = -np.ones(C, np.int32)
        new_sop = -np.ones(P, np.int32)
        for i, c in enumerate(keep):
            perm[i] = c
            new_ros[i] = ros[c]
            new_sop[ros[c]] = i
        if "near_k" in cache:
            shape = cache["near_k"].shape
            nk = cache["near_k"].reshape(C, page, *shape[1:])
            nv = cache["near_v"].reshape(C, page, *shape[1:])
            cache["near_k"] = jnp.take(nk, perm, axis=0).reshape(shape)
            cache["near_v"] = jnp.take(nv, perm, axis=0).reshape(shape)
        sop, ros = new_sop, new_ros
    sop[pages] = -1
    cache["scores"] = jnp.asarray(scores)
    cache["last_use"] = jnp.asarray(last_use)
    cache["slot_of_page"] = jnp.asarray(sop)
    cache["page_of_slot"] = jnp.asarray(ros)
    return cache


def refresh_near_from_pool(pool_k: jax.Array, pool_v: jax.Array,
                           page_of_slot: jax.Array):
    """Re-derive near-tier buffers from the pool under the current global
    near mapping — the pool-native near refresh (the pool IS the master
    copy, so a full re-gather is equivalent to incremental page copies).

    pool_k/pool_v: (..., P, page, Hkv, hd) — a leading layer axis is
    supported (the serving engine keeps per-layer pools).  Returns
    (near_k, near_v) of shape (..., C*page, Hkv, hd); unoccupied near
    slots come out zeroed.  Runs only when the mapping changes
    (plan / pin / release / admit / retire), never per decode step."""
    safe = jnp.maximum(page_of_slot, 0)
    occ = page_of_slot >= 0
    nk = jnp.take(pool_k, safe, axis=-4)
    nv = jnp.take(pool_v, safe, axis=-4)
    occ_b = occ[(...,) + (None,) * 3]
    nk = jnp.where(occ_b, nk, 0)
    nv = jnp.where(occ_b, nv, 0)
    *lead, C, page, Hkv, hd = nk.shape
    shape = (*lead, C * page, Hkv, hd)
    return nk.reshape(shape), nv.reshape(shape)
