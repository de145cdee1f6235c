"""Continuous-batching tiered-KV serving engine (the TL-DRAM runtime).

The paper's near segment only pays off when many concurrent accesses share
the fast path; the serving analogue is a *slot pool*: a fixed batch of
decode slots that independent sequences are admitted into and retired from,
so one batched decode step serves every in-flight sequence at once (ragged
``pos`` — each slot sits at its own position).

Since ISSUE 5 the **page pool is the single source of truth** for KV bytes
(docs/design.md §2f).  The TL-DRAM near segment is not a duplicate of far
rows — the isolation transistor splits one bitline so the same array serves
both tiers — and the serving stack now honors that: there is no dense
per-slot KV master.  Prefill scatters straight into allocated pool pages,
decode appends write through the page table (``paged_step_metadata``'s
append routing), and scoring / planning / pinning / the verification probe
all read the pool.  Each slot maps only the pages its request can ever
touch (``ceil((S + max_new - 1)/page)``), so live KV bytes track demand,
not slot capacity — ``ServingReport.kv_bytes_live`` vs
``kv_bytes_dense_equiv`` pins the ratio.

A radix prefix cache (``serve.prefix``) lets admissions reuse
already-written pages for shared prompt prefixes — refcount++, prefill
**only the suffix** (the modeled clock and the real compute both drop), and
the suffix-chunked prefill reproduces the full-prefill cache rows
bit-identically.  The near tier is global: a hot shared page is scored by
the aggregate attention mass of every referencing sequence and promoted
ONCE for all tenants — the paper's one-IST-many-accesses economics.

Scheduler loop (``ServingEngine.run``; docs/design.md §2g for the
ISSUE 8 overlapped-tick pipeline):

  admit    : pop arrived requests into free slots — match the prompt
             against the radix prefix cache, map shared pages, and either
             prefill the suffix straight into fresh pool pages (one
             jitted program) and seed the first token (synchronous mode),
             or, with ``prefill_chunk_tokens`` set, enqueue a
             ``_PrefillJob`` — pages allocated now, NO prefill compute:
             the prompt fills in over later ticks' chunk budgets.
  prefill  : (chunked mode) advance pending jobs FIFO up to the tick
             budget (halved while active slots exceed half the pool),
             each job resuming from its saved cursor into its own pages
             via the chunk-resume step — bit-identical rows to a one-shot
             prefill.  Chunk tokens piggyback on the decode tick's cost
             (per-token only, no second step_overhead); a pending slot's
             device page-table row stays -1, so the decode lane treats it
             exactly like a free slot until the job completes.
  decode   : ONE batched ``transformer.paged_decode_step`` with per-slot
             ``pos`` emits a token for every active slot, appending K/V
             through the page table into the pool — via the fused
             page-table-walking kernel (``tier.fused_kernel``) or the
             materializing oracle path (bit-identical logits to the
             retired PR-4 dense-master path).
  maintain : every ``tier.interval`` decode steps, score per-page
             attention mass with the step's layer-0 queries
             (pool-natively — the fused mode scores through
             `kernels.paged_masses`, no far-view gather), aggregate it
             onto pool pages, and run the configured policy (SC/WMC/BBC
             via ``paged_plan_and_migrate``; STATIC pins each slot once
             at its first interval) — the amortized IST.  The pass is
             cost-aware for ALL policies: while the run queue is hot
             (pending chunks or waiting arrivals) it defers, at most
             ``defer_limit`` passes in a row.  Mapping changes re-derive
             the per-layer near buffers from the pool
             (``refresh_near_from_pool``); with ``overlap_migration`` the
             copies land in a shadow buffer swapped at the next tick
             boundary, and migration bytes bill a background lane that
             stalls the clock only when saturated.
  retire   : finished sequences release their page refs; pages at refcount
             zero are freed unless the prefix cache retains them for
             re-arrivals.  At run end a refcount sweep asserts ZERO
             orphaned pages (every page free, referenced-by-nobody, or
             trie-retained — and nothing else).

The decode path is *exact* (full-live-prefix attention in both read
modes), so emitted tokens match the single-sequence ``greedy_generate``
reference bit-for-bit with sharing on or off (tests/test_prefix_sharing.py,
tests/test_serving_engine.py); the paged tiered state drives the byte-cost
model and, optionally, a pool-native read-path verification probe
(``verify_tiered_read``).

What the tick loop does on the host is observable from inside: ``run``
writes constant-named ``serve.*`` spans on the profiler's host plane
(``_span``; the device planes share its clock), every blocking read of a
device value goes through ``_host`` (a ``serve.wait.<what>`` span and one
``ServingReport.host_syncs``), and the report records each decode tick's
wall times and occupancy, each prefill chunk's rows and each request's
wall times.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import tiered_kv as tkv
from repro.core.tiered_kv import PagePool, TieredKVConfig
from repro.kernels import ref
from repro.models import transformer
from repro.serve.metrics import CostModel, ServingReport, merge_lane_reports
from repro.sharding.specs import cache_specs, kv_shard_count, to_named
from repro.serve.prefix import RadixPrefixCache
from repro.serve.trace import Request

# the mapping-only tier-state leaves the engine owns (pool/near buffers are
# separate per-layer arrays — the ownership inversion)
_TIER_KEYS = ("page_table", "slot_of_page", "page_of_slot", "scores",
              "last_use", "step", "migrations")
# the leaves ``tkv.paged_release_pages`` edits on the host
_RELEASE_KEYS = ("scores", "last_use", "slot_of_page", "page_of_slot")

# Spans on the profiler's host plane, on the clock of its device planes.
# Names are constant strings (trace reductions match them exactly):
#   serve.run > serve.setup | serve.tick | serve.teardown
#   serve.tick > serve.admit | serve.prefill | serve.decode | serve.maintain
#   serve.wait.<what>: one blocking read of a device value (``_host``)
_span = jax.profiler.TraceAnnotation


@dataclass
class ServingConfig:
    n_slots: int = 4
    max_len: int = 256
    prefill_bucket: int = 32      # prompt lengths pad up to a multiple of
                                  # this (bounds jit recompiles; exact —
                                  # causal attention ignores the pad tail)
    tier: TieredKVConfig = field(default_factory=TieredKVConfig)
    cost: CostModel = field(default_factory=CostModel)
    share_prefix: bool = False    # radix prefix cache over the page pool:
                                  # admissions reuse shared prompt pages and
                                  # prefill only the suffix
    pool_pages: int | None = None  # far-pool capacity; default covers every
                                   # slot fully plus retention slack for the
                                   # prefix cache
    verify_tiered_read: bool = False   # probe paged tiered read vs
                                       # attention over the materialized
                                       # pool view at every planning pass
    # -- overlap knobs (ISSUE 8 tentpole) ------------------------------------
    prefill_chunk_tokens: int | None = None
                                  # budget of admission-prefill tokens run
                                  # per tick, interleaved with the batched
                                  # decode step (Sarathi-style chunked
                                  # prefill).  None = legacy synchronous
                                  # admission: the whole prompt prefills
                                  # inside the admitting tick and every
                                  # in-flight request stalls behind it.
    overlap_migration: bool = False
                                  # charge migration bytes to a background
                                  # lane that only adds latency when
                                  # saturated, and double-buffer the near
                                  # tier (promotion copies land in a shadow
                                  # buffer, swapped at the tick boundary)
    defer_limit: int = 2          # cost-aware deferral gate (the WMC
                                  # queue-idle gate generalized to all four
                                  # policies): consecutive planning passes
                                  # skippable while the run queue is hot


@dataclass
class _Slot:
    req: Request
    emitted: list
    last_emit: float              # modeled clock of the last emitted token


@dataclass
class _PrefillJob:
    """A chunk-resumable admission prefill in flight: pages are allocated
    (and refcounted — real bytes) up front, the cursor tracks prompt rows
    already written to the pool, and the slot's device page table stays
    unmapped until completion so decode appends sentinel-drop and
    scoring/planning ignore the slot, exactly like a free one."""
    req: Request
    prompt: np.ndarray            # (S,) int32
    S: int
    row: list                     # full page mapping (matched + fresh)
    n_need: int
    matched: int                  # prompt tokens served by the prefix cache
    cursor: int                   # prompt rows already written to the pool


class ServingEngine:
    def __init__(self, params, arch: ArchConfig, cfg: ServingConfig):
        assert arch.n_heads and arch.ssm is None, \
            "serving engine requires an attention-family architecture"
        assert not arch.sliding_window, \
            "ragged slot pool + ring buffer not supported yet"
        assert cfg.max_len % cfg.tier.page == 0, \
            "max_len must be a page multiple"
        assert not (cfg.share_prefix and arch.mrope), \
            "prefix sharing needs 1-D positions"
        self.params, self.arch, self.cfg = params, arch, cfg
        self.n_pages = cfg.max_len // cfg.tier.page
        tier_cfg = cfg.tier
        # fused mode (ISSUE 4/5): reads walk the page table in-kernel and
        # scoring runs the pool-native mass kernel; the non-fused mode
        # materializes per-layer far views from the SAME pool (the oracle)
        self.fused = bool(tier_cfg.fused_kernel)
        # mesh-native serving (docs/design.md §2h): with tier.mesh set and
        # Hkv divisible by the 'model' axis, the pool/near buffers are
        # KV-head-sharded and every device streams 1/kv_shards of the KV
        # bytes per decode step (the cost model's kv_shards lane).  The
        # GQA/MQA fallback (kv_shards == 1) keeps everything replicated.
        self.mesh = tier_cfg.mesh
        self.kv_shards = kv_shard_count(self.mesh, arch.n_kv_heads)
        # Pool sizing: worst case (no sharing) every slot maps private
        # pages; the slack keeps retired prompts cached for re-arrivals.
        self.pool_pages = cfg.pool_pages if cfg.pool_pages is not None \
            else (cfg.n_slots + 4) * self.n_pages
        assert self.pool_pages >= cfg.n_slots * self.n_pages, \
            "pool must at least cover the slot pool"
        P = self.pool_pages

        if cfg.prefill_chunk_tokens is not None:
            assert cfg.prefill_chunk_tokens >= tier_cfg.page, \
                "prefill_chunk_tokens must cover at least one page"

        from repro.launch.serve import (make_paged_tiered_decode_step,
                                        make_pool_chunk_prefill_step,
                                        make_pool_prefill_step,
                                        make_pool_suffix_prefill_step)
        # every program that writes the pool DONATES it (and the decode
        # step its whole KV cache): the engine rebinds the outputs at once,
        # so the pool is updated in place instead of living twice in HBM
        self._decode = jax.jit(make_paged_tiered_decode_step(arch, tier_cfg),
                               donate_argnums=(1,))
        # per-step read metadata, computed ONCE per tick and shared by
        # every layer: lengths = pos + 1 (the appended token is live),
        # append routing from pos
        self._meta = jax.jit(
            lambda tier, pos: tkv.paged_step_metadata(
                tier, pos + 1, tier_cfg, append_pos=pos, pool_pages=P))

        def _view(tier, pk, pv, nk, nv):
            """Single-layer tiered_kv view over layer 0 of the per-layer
            buffers (sliced inside jit: lazy, and unused slices are DCE'd).
            Layer 0 is representative — every layer shares one page table
            and one near mapping; the scoring query is layer 0's."""
            return {**tier, "pool_k": pk[0], "pool_v": pv[0],
                    "near_k": nk[0], "near_v": nv[0]}

        def _plan_fn(tier, pk, pv, nk, nv, q, pos, idle, m):
            new = tkv.paged_plan_and_migrate(
                _view(tier, pk, pv, nk, nv), q, pos, tier_cfg, idle=idle,
                masses=m)
            return {k: new[k] for k in _TIER_KEYS}

        self._plan = jax.jit(_plan_fn)
        self._masses = jax.jit(
            lambda q, tier, pk, pv, pos: tkv.paged_page_masses(
                q, {**tier, "pool_k": pk[0], "pool_v": pv[0]}, pos,
                tier_cfg))

        probe_cfg = dataclasses.replace(tier_cfg, gather_kernel=False,
                                        fused_kernel=False)

        def _probe_fn(tier, pk, pv, nk, nv, q, pos):
            view = _view(tier, pk, pv, nk, nv)
            got = tkv.paged_tiered_attention(view, q, pos, tier_cfg)
            far_k, far_v = tkv.paged_far_view(view, probe_cfg)
            want = ref.decode_attention_ref(q[:, None], far_k, far_v,
                                            pos)[:, 0]
            return got, want

        self._probe = jax.jit(_probe_fn)
        self._sync_near = jax.jit(tkv.refresh_near_from_pool)
        # jax.jit caches per input shape, so one wrapper covers every
        # prompt-length bucket (and every matched-prefix length)
        self._prefill = jax.jit(
            make_pool_prefill_step(arch, cfg.max_len, tier_cfg.page,
                                   mesh=self.mesh), donate_argnums=(2, 3))
        self._prefill_sfx = jax.jit(
            make_pool_suffix_prefill_step(arch, cfg.max_len, tier_cfg.page,
                                          mesh=self.mesh),
            donate_argnums=(4, 5))
        # chunk-resumable admission prefill: t_pre (the cursor) is static —
        # it sizes the in-jit prefix slice; jit caches per (t_pre, s_pad)
        self._prefill_chunk = jax.jit(
            make_pool_chunk_prefill_step(arch, cfg.max_len, tier_cfg.page,
                                         mesh=self.mesh),
            static_argnames=("t_pre",), donate_argnums=(2, 3))
        page = tier_cfg.page

        def gather_prefix(pool_k, pool_v, ids):
            """(L,P,page,Hkv,hd) pools + (m,) ids -> (L,1,m*page,Hkv,hd)."""
            k = pool_k[:, ids]
            L, m, _, Hkv, hd = k.shape
            return (k.reshape(L, 1, m * page, Hkv, hd),
                    pool_v[:, ids].reshape(L, 1, m * page, Hkv, hd))

        self._gather_prefix = jax.jit(gather_prefix)

    # -- admission ----------------------------------------------------------

    def _map_request(self, req: Request):
        """The mapping steps shared by both admission paths.

        1. prefix match: reuse already-written pool pages (refcount++).
           match() caps at (S-1)//page pages <= n_need - 1, so at least
           one fresh page always remains for the suffix.
        2. map ONLY the pages this request can ever touch onto fresh pages
           (evicting LRU cached-idle pages under pressure; their tier
           state resets): prefill writes [0, S), decode appends reach at
           most S + max_new - 2 (the final emitted token is never
           appended) — live KV bytes track demand."""
        cfg = self.cfg
        page = cfg.tier.page
        self.report.request_wall[req.rid][1] = time.perf_counter()
        prompt = np.asarray(req.prompt, np.int32)
        S = int(prompt.shape[0])
        assert S + req.max_new_tokens <= cfg.max_len, \
            f"request {req.rid} does not fit max_len={cfg.max_len}"
        n_need = max(1, -(-(S + req.max_new_tokens - 1) // page))
        matched_ids = [] if self.prefix is None \
            else self.prefix.match(prompt)
        m = len(matched_ids)
        if m:
            self.pool.acquire(matched_ids)
        if self.prefix is not None:
            fresh, evicted = self.prefix.allocate(n_need - m)
            if evicted:
                self._release(evicted)
                self._after_mapping_change()   # eviction compacts the near
                                               # mapping: shadow is stale
        else:
            fresh = self.pool.allocate(n_need - m)
        return prompt, S, n_need, matched_ids + fresh, m

    def _admit(self, req: Request, slot: int, clock: float) -> float:
        cfg = self.cfg
        page = cfg.tier.page
        prompt, S, n_need, row, m = self._map_request(req)
        matched = m * page
        self.pt_host[slot] = -1
        self.pt_host[slot, :n_need] = row
        self.tier["page_table"] = self.tier["page_table"].at[slot].set(
            jnp.asarray(self.pt_host[slot], jnp.int32))

        # 3. prefill ONLY the suffix (bucket-padded) STRAIGHT INTO the
        #    slot's fresh pool pages — one jitted program; the dense rows
        #    are a transient inside it.  Shared-prefix K/V comes from the
        #    pool; real compute drops with ``matched``.
        s_len = S - matched
        s_pad = -(-s_len // cfg.prefill_bucket) * cfg.prefill_bucket
        padded = np.zeros((1, s_pad), np.int32)
        padded[0, :s_len] = prompt[matched:]
        ids = -np.ones(self.n_pages, np.int32)
        ids[m:n_need] = row[m:]
        ids = jnp.asarray(ids)
        if m:
            kpre, vpre = self._gather_prefix(
                self.pool_k, self.pool_v,
                jnp.asarray(row[:m], jnp.int32))
            positions = matched + np.arange(s_pad, dtype=np.int32)[None]
            logits, self.pool_k, self.pool_v = self._prefill_sfx(
                self.params, {"tokens": padded, "positions": positions},
                kpre, vpre, self.pool_k, self.pool_v, ids)
        else:
            logits, self.pool_k, self.pool_v = self._prefill(
                self.params, {"tokens": padded}, self.pool_k, self.pool_v,
                ids)
        first = int(self._host(jnp.argmax(logits[0, s_len - 1]),
                               "serve.wait.first_token"))
        self._wall_first(req.rid)

        # 4. index the prompt's new full pages for later sharers — they are
        #    already in the pool (prefill wrote them); no re-gather
        if self.prefix is not None:
            n_full = S // page
            if n_full > m:
                self.prefix.insert(prompt[:n_full * page], row[:n_full])
        self._after_mapping_change()

        self.pos[slot] = S
        self.tok[slot] = first
        self._static_pinned[slot] = False
        clock += cfg.cost.prefill_cost(s_len)
        self.slots[slot] = _Slot(req=req, emitted=[first], last_emit=clock)
        ttft = clock - self._visible_clock[req.rid]
        self.report.token_latencies.append(ttft)
        self.report.ttfts.append(ttft)
        self.report.tokens += 1
        self.report.prefill_tokens += s_len
        self.report.prefill_tokens_full += S
        self.report.prefix_hit_tokens += matched
        self.slot_history.setdefault(slot, []).append(req.rid)
        return clock

    # -- chunked admission (ISSUE 8 tentpole) --------------------------------

    def _admit_chunked(self, req: Request, slot: int):
        """Reserve the slot and map the request's pages NOW (cheap,
        host-side), but run NO prefill compute: the prompt fills in over
        the next ticks' chunk budgets, overlapped with decode.  The device
        page table stays unmapped until completion."""
        prompt, S, n_need, row, m = self._map_request(req)
        matched = m * self.cfg.tier.page
        self.pending[slot] = _PrefillJob(req=req, prompt=prompt, S=S,
                                         row=row, n_need=n_need,
                                         matched=matched, cursor=matched)
        self.report.prefill_tokens_full += S
        self.report.prefix_hit_tokens += matched
        self.slot_history.setdefault(slot, []).append(req.rid)

    def _prefill_budget(self, n_active: int) -> int:
        """This tick's chunk budget, shrunk by the cost-aware gate when the
        tick is decode-heavy: serving more than half the slot pool halves
        the prefill lane so admission work cannot crowd out in-flight
        inter-token latency (floor: one page)."""
        budget = self.cfg.prefill_chunk_tokens
        if n_active > self.cfg.n_slots // 2:
            budget = max(self.cfg.tier.page, budget // 2)
        return budget

    def _advance_prefills(self, budget: int) -> tuple[int, list]:
        """Run at most ``budget`` prompt tokens of pending admission
        prefills, FIFO, each job resuming from its saved cursor into its
        already-allocated pool pages.  The boundary page of a mid-page
        cursor is rewritten whole — an identity for rows below the cursor
        (the chunk step's prefix rows ARE the pool bytes), so coverage
        grows monotonically and the final rows are bit-identical to a
        one-shot prefill.  Completed full pages are trie-inserted
        immediately, so arrivals overlapping a still-chunking prompt can
        already share it.  Returns (chunk_tokens, completions) with
        completions = [(slot, job, first_token)] for jobs reaching S."""
        cfg = self.cfg
        page = cfg.tier.page
        chunk_toks, done = 0, []
        for slot, job in list(self.pending.items()):
            take = min(budget - chunk_toks, job.S - job.cursor)
            if take <= 0:
                break                       # FIFO: no skipping ahead
            c0, n = job.cursor, take
            s_pad = -(-n // cfg.prefill_bucket) * cfg.prefill_bucket
            padded = np.zeros((1, s_pad), np.int32)
            padded[0, :n] = job.prompt[c0:c0 + n]
            p_lo = c0 // page               # first page not yet complete
            p_hi = min(-(-(c0 + n) // page), job.n_need)
            ids = -np.ones(self.n_pages, np.int32)
            ids[p_lo:p_hi] = job.row[p_lo:p_hi]
            ids = jnp.asarray(ids)
            if c0 == 0:
                logits, self.pool_k, self.pool_v = self._prefill(
                    self.params, {"tokens": padded}, self.pool_k,
                    self.pool_v, ids)
            else:
                positions = c0 + np.arange(s_pad, dtype=np.int32)[None]
                prefix_ids = jnp.asarray(job.row[:-(-c0 // page)], jnp.int32)
                logits, self.pool_k, self.pool_v = self._prefill_chunk(
                    self.params, {"tokens": padded, "positions": positions},
                    self.pool_k, self.pool_v, prefix_ids, ids, t_pre=c0)
            self.report.prefill_rows.append((c0, c0 + n))
            job.cursor += n
            chunk_toks += n
            self.report.prefill_tokens += n
            self.report.prefill_chunks += 1
            if self.prefix is not None:
                n_full = job.cursor // page
                if n_full > job.matched // page:
                    self.prefix.insert(job.prompt[:n_full * page],
                                       job.row[:n_full])
            if job.cursor >= job.S:
                first = int(self._host(jnp.argmax(logits[0, n - 1]),
                                       "serve.wait.first_token"))
                self._wall_first(job.req.rid)
                done.append((slot, job, first))
                del self.pending[slot]
        return chunk_toks, done

    def _complete_prefill(self, slot: int, job: _PrefillJob, first: int,
                          clock: float):
        """Install a finished prompt: the page table goes live (decode
        appends route through it from the next tick), the slot activates,
        and the final chunk's last-row logits seed the first token.  TTFT
        is the clock at the completing tick minus the request's visible
        arrival — queueing plus chunked prefill."""
        self.pt_host[slot] = -1
        self.pt_host[slot, :job.n_need] = job.row
        self.tier["page_table"] = self.tier["page_table"].at[slot].set(
            jnp.asarray(self.pt_host[slot], jnp.int32))
        self._after_mapping_change()
        self.pos[slot] = job.S
        self.tok[slot] = first
        self._static_pinned[slot] = False
        self.slots[slot] = _Slot(req=job.req, emitted=[first],
                                 last_emit=clock)
        ttft = clock - self._visible_clock[job.req.rid]
        self.report.token_latencies.append(ttft)
        self.report.ttfts.append(ttft)
        self.report.tokens += 1

    def _retire(self, slot: int):
        st = self.slots[slot]
        self.report.outputs[st.req.rid] = list(st.emitted)
        self.slots[slot] = None
        self.pos[slot] = 0
        self.tok[slot] = 0
        self._near_tokens[slot] = 0
        # drop this slot's page references NOW, not at the next admit: freed
        # pages' decayed scores would otherwise stay promotion-eligible and
        # keep the planning pass migrating (and billing) stale pages.
        # Prefix-cached pages survive at refcount zero (re-arrival hits) —
        # including their near-tier residency.
        pids = [int(p) for p in self.pt_host[slot] if p >= 0]
        freed = self.pool.release(pids)
        if freed:
            self._release(freed)
        self.pt_host[slot] = -1
        self.tier["page_table"] = self.tier["page_table"].at[slot].set(-1)
        self._after_mapping_change()
        self.free.append(slot)
        self.free.sort()

    # -- pool-native bookkeeping ---------------------------------------------

    def _host(self, x, span: str) -> np.ndarray:
        """Every blocking read of a device value on the tick path goes
        through here: the host waits for ``x`` inside the ``span``
        (``serve.wait.<what>``), and ``ServingReport.host_syncs`` counts
        the read."""
        with _span(span):
            out = np.asarray(x)
        self.report.host_syncs += 1
        return out

    def _wall_first(self, rid: int):
        """The request's first token is on the host now (it is also its
        last until decode emits another)."""
        wall = self.report.request_wall[rid]
        wall[2] = wall[3] = time.perf_counter()

    def _release(self, pages):
        """Reset the tier state of pool pages leaving allocation: the host
        surgery of ``tkv.paged_release_pages`` on read-back copies of the
        leaves it edits."""
        host = {k: self._host(self.tier[k], "serve.wait.mapping")
                for k in _RELEASE_KEYS}
        self.tier = tkv.paged_release_pages({**self.tier, **host}, pages,
                                            self.cfg.tier)

    def _after_mapping_change(self):
        """Mark the per-layer near buffers / host residency mirror stale
        after any event that moves the global near mapping or the page
        tables (plan / pin / release / admit / retire).  The actual re-sync
        happens once per tick (``_flush_mapping``) — N retires + M admits
        in one tick cost one gather, not N+M.  Any such event also
        invalidates the shadow near buffer (it was derived from the
        previous mapping)."""
        self._mapping_dirty = True
        self._shadow_near = None

    def _flush_mapping(self):
        if not self._mapping_dirty:
            return
        if self._shadow_near is not None:
            # double-buffered near tier (ISSUE 8): the promotion copies
            # were dispatched right after planning and drained behind the
            # tick's host work — swap at the tick boundary instead of
            # re-gathering on the critical path.  The shadow stayed valid
            # because only COMPLETE pages promote: decode appends and
            # prefill chunks never write a near-resident page.
            self.near_k, self.near_v = self._shadow_near
            self._shadow_near = None
        else:
            self.near_k, self.near_v = self._sync_near(
                self.pool_k, self.pool_v, self.tier["page_of_slot"])
        sop = self._host(self.tier["slot_of_page"], "serve.wait.mapping")
        self._promoted_host = (self.pt_host >= 0) \
            & (sop[np.maximum(self.pt_host, 0)] >= 0)
        self._near_used = int((self._host(self.tier["page_of_slot"],
                                          "serve.wait.mapping") >= 0).sum())
        self._mapping_dirty = False

    def _far_rows_shadow(self) -> int:
        """Host-side recomputation of the fused step's far rows touched:
        per slot, the live rows of its mapped, non-promoted pages (lengths
        = pos + 1: the token appended this step is attended)."""
        lengths = self.pos + 1
        page_start = np.arange(self.n_pages) * self.cfg.tier.page
        live = np.clip(lengths[:, None] - page_start[None, :], 0,
                       self.cfg.tier.page)
        walk = (self.pt_host >= 0) & ~self._promoted_host
        return int((live * walk).sum())

    def _account_kv_bytes(self):
        """Track peak LIVE KV bytes: referenced pool pages ONLY, across all
        layers, K and V.  The near tier holds *derived copies* of pool
        bytes (TL-DRAM's near segment is the same mat behind the isolation
        transistor, not extra capacity) — accounted in ``kv_bytes_near``,
        never against the dense-equiv denominator, which never included a
        near tier either (the kv_live_ratio 1.042 bench lie, ISSUE 8).
        Trie-retained idle pages are reclaimable cache
        (``kv_bytes_cached``).

        ``live <= dense_equiv`` is an engine invariant asserted every
        tick: each slot (or pending prefill job) maps at most
        ``ceil((S + max_new - 1)/page) <= n_pages`` pages and shared pages
        are counted once."""
        item = self.pool_k.dtype.itemsize
        row = self.arch.n_kv_heads * self.arch.resolved_head_dim * item * 2
        L = self.arch.n_layers
        page = self.cfg.tier.page
        ref_pages = int((self.pool.refcount > 0).sum())
        live = L * ref_pages * page * row
        assert live <= self.report.kv_bytes_dense_equiv, (
            f"kv_live invariant violated: {live} referenced-pool bytes > "
            f"dense-equiv {self.report.kv_bytes_dense_equiv} "
            f"({ref_pages} pages referenced)")
        cached = int(((self.pool.refcount == 0) & self.pool.cached).sum())
        self.report.kv_bytes_live = max(self.report.kv_bytes_live, live)
        self.report.kv_bytes_near = max(
            self.report.kv_bytes_near, L * self._near_used * page * row)
        self.report.kv_bytes_cached = max(self.report.kv_bytes_cached,
                                          L * cached * page * row)

    def _assert_zero_orphans(self):
        """Refcount sweep at engine shutdown (ISSUE 5 satellite): with all
        sequences retired, every pool page must be free, or retained by the
        prefix trie — anything still referenced (or cached outside the
        trie) is an orphan the release path leaked."""
        leaked = np.flatnonzero(self.pool.refcount > 0)
        if leaked.size:
            raise RuntimeError(
                f"orphaned pool pages at shutdown (refcount > 0 with no "
                f"live slot): {leaked.tolist()}")
        cached = set(np.flatnonzero(self.pool.cached).tolist())
        trie = set() if self.prefix is None else self.prefix.cached_pages()
        if cached != trie:
            raise RuntimeError(
                f"retention flags diverge from the prefix trie: "
                f"cached-not-in-trie {sorted(cached - trie)}, "
                f"trie-not-cached {sorted(trie - cached)}")
        free = set(int(p) for p in self.pool._free)
        if (free | cached) != set(range(self.pool_pages)) or (free & cached):
            raise RuntimeError("free list + trie retention do not "
                               "partition the pool at shutdown")

    # -- background tier maintenance ----------------------------------------

    def _bill_migration(self, clock: float, pages_moved: int) -> float:
        """Charge migration bytes to the modeled clock.  Synchronous mode:
        the decode clock pays immediately (the pre-ISSUE-8 stall).
        Overlapped mode: the copies drain on a background lane — the clock
        stalls only while the lane is still busy with the previous batch
        (saturation), then the lane stays busy for this batch's cost."""
        cost = self.cfg.cost.migration_cost(pages_moved, self.cfg.tier.page)
        if not self.cfg.overlap_migration:
            return clock + cost
        stall = max(0.0, self._lane_free - clock)
        self.report.migration_stall += stall
        clock += stall
        self._lane_free = clock + cost
        return clock

    def _pin_static(self, masses: np.ndarray, need: np.ndarray,
                    clock: float) -> float:
        """STATIC: at a slot's first planning interval, place its hottest
        complete pages into FREE global near slots (profile placement — no
        later migration, no eviction of earlier pins)."""
        cfg = self.cfg
        tier = cfg.tier
        ros = self._host(self.tier["page_of_slot"], "serve.wait.mapping")
        sop = self._host(self.tier["slot_of_page"], "serve.wait.mapping")
        free_slots = [c for c in range(ros.shape[0]) if ros[c] < 0]
        complete = ((np.arange(self.n_pages)[None, :] + 1) * tier.page
                    <= self.pos[:, None])
        cand_mass: dict[int, float] = {}
        for b in np.flatnonzero(need):
            for j in range(self.n_pages):
                p = int(self.pt_host[b, j])
                if p >= 0 and complete[b, j] and masses[b, j] > 0 \
                        and sop[p] < 0:
                    cand_mass[p] = cand_mass.get(p, 0.0) + float(masses[b, j])
        ranked = sorted(cand_mass, key=lambda p: -cand_mass[p])
        chosen = ranked[:len(free_slots)]
        if chosen:
            self.tier = tkv.paged_pin_pages(self.tier, chosen,
                                            free_slots[:len(chosen)], tier)
            clock = self._bill_migration(clock, len(chosen))
            self.report.migrations += len(chosen)  # pin copies are ISTs too
        self._static_pinned |= need
        return clock

    def _maintain(self, q0, clock: float, idle: bool) -> float:
        cfg = self.cfg
        tier = cfg.tier
        active = np.array([s is not None for s in self.slots])
        pos_vec = jnp.asarray(self.pos, jnp.int32)
        # one scoring pass per interval, straight off the pool (fused mode:
        # the pool-native mass kernel — no far-view gather); the same
        # per-slot masses drive planning/pinning AND the hit-mass metric
        masses_dev = self._masses(q0, self.tier, self.pool_k, self.pool_v,
                                  pos_vec)
        if tier.policy.upper() == "STATIC":
            need = active & ~self._static_pinned
            if need.any():
                clock = self._pin_static(
                    self._host(masses_dev, "serve.wait.masses"), need, clock)
                self._after_mapping_change()
        else:
            before = int(self._host(self.tier["migrations"],
                                    "serve.wait.migrations"))
            self.tier = self._plan(self.tier, self.pool_k, self.pool_v,
                                   self.near_k, self.near_v, q0, pos_vec,
                                   idle, masses_dev)
            moved = int(self._host(self.tier["migrations"],
                                   "serve.wait.migrations")) - before
            clock = self._bill_migration(clock, moved)
            self.report.migrations += moved
            if moved:     # mapping unchanged when nothing migrated
                self._after_mapping_change()
        if self._mapping_dirty:
            # dispatch the near re-derivation NOW (async): the scatter runs
            # behind this tick's emit/retire host work, and _flush_mapping
            # swaps it in at the next tick boundary — the double buffer
            self._shadow_near = self._sync_near(
                self.pool_k, self.pool_v, self.tier["page_of_slot"])
        sop = self._host(self.tier["slot_of_page"], "serve.wait.mapping")
        promoted = (self.pt_host >= 0) & (sop[np.maximum(self.pt_host, 0)]
                                          >= 0)              # (B, n_pages)
        self._near_tokens = promoted.sum(axis=1) * tier.page
        # near-tier hit mass over active slots (the paper's near-segment hit
        # rate, in attention-mass units) — a shared promoted page counts for
        # every referencing slot: one IST, many accesses
        if active.any():
            masses = self._host(masses_dev, "serve.wait.masses")
            tot = masses[active].sum()
            if tot > 0:
                self.report.near_hit_mass.append(
                    float((masses * promoted)[active].sum() / tot))
            if cfg.verify_tiered_read:
                self._flush_mapping()   # the probe reads the near buffers
                got, want = self._probe(self.tier, self.pool_k, self.pool_v,
                                        self.near_k, self.near_v, q0,
                                        pos_vec)
                err = float(self._host(
                    jnp.max(jnp.abs((got - want)[jnp.asarray(active)])),
                    "serve.wait.probe"))
                self.report.max_read_err = max(self.report.max_read_err, err)
        return clock

    # -- driver --------------------------------------------------------------

    def run(self, trace: list[Request], scenario: str = "trace") -> ServingReport:
        """Replay an offline arrival trace to completion."""
        with _span("serve.run"):
            with _span("serve.setup"):
                self._start(len(trace), scenario)
            t0 = time.perf_counter()
            clock, steps = self._serve(
                deque(sorted(trace, key=lambda r: (r.arrival, r.rid))))
            with _span("serve.teardown"):
                if self.cfg.overlap_migration:
                    # the background lane finishes draining after the last
                    # token
                    clock = max(clock, self._lane_free)
                self._assert_zero_orphans()
                self.report.steps = steps
                self.report.wall_s = time.perf_counter() - t0
                self.report.modeled_time = clock
                self.report.slot_history = dict(self.slot_history)
                if self.prefix is not None:
                    self.report.prefix_lookups = self.prefix.stats.lookups
                    self.report.prefix_hits = self.prefix.stats.hits
            return self.report

    def _start(self, n_requests: int, scenario: str):
        """A fresh report, an empty pool and near tier, and the host
        mirrors of the slot pool."""
        cfg = self.cfg
        arch = self.arch
        self.report = ServingReport(scenario=scenario,
                                    policy=cfg.tier.policy,
                                    n_requests=n_requests)
        hd = arch.resolved_head_dim
        dtype = jnp.bfloat16
        # THE KV store: per-layer shared page pool + per-layer global near
        # buffer.  No dense per-slot master exists anywhere in this engine.
        pshape = (arch.n_layers, self.pool_pages, cfg.tier.page,
                  arch.n_kv_heads, hd)
        nshape = (arch.n_layers, cfg.tier.near_pages * cfg.tier.page,
                  arch.n_kv_heads, hd)

        def zeros():
            return {"pool_k": jnp.zeros(pshape, dtype),
                    "pool_v": jnp.zeros(pshape, dtype),
                    "near_k": jnp.zeros(nshape, dtype),
                    "near_v": jnp.zeros(nshape, dtype)}

        if self.kv_shards > 1:
            # build the pool/near buffers on their KV-head sharding, so each
            # device allocates only its head slice (a whole pool built on
            # one device first would have to fit there) and every jitted
            # step consumes and produces the sharded layout
            named = to_named(cache_specs(jax.eval_shape(zeros), arch,
                                         self.mesh), self.mesh)
            kv = jax.jit(zeros, out_shardings=named)()
        else:
            kv = zeros()
        self.pool_k, self.pool_v = kv["pool_k"], kv["pool_v"]
        self.near_k, self.near_v = kv["near_k"], kv["near_v"]
        self.tier = tkv.init_tier_state(cfg.n_slots, self.n_pages,
                                        self.pool_pages, cfg.tier.near_pages)
        self.pool = PagePool(self.pool_pages)
        self.prefix = RadixPrefixCache(self.pool, cfg.tier.page) \
            if cfg.share_prefix else None
        # host mirror of per-(slot, page) near residency, re-synced (with
        # the near buffers) once per tick when the mapping moved — drives
        # the independent shadow accounting of far rows touched
        self._promoted_host = np.zeros((cfg.n_slots, self.n_pages), bool)
        self._mapping_dirty = False
        self._shadow_near = None
        self._near_used = 0
        self.pending: dict[int, _PrefillJob] = {}
        self._lane_free = 0.0         # background migration lane drains at
        self._defer_count = 0         # consecutive deferred planning passes
        self.pt_host = -np.ones((cfg.n_slots, self.n_pages), np.int64)
        self.pos = np.zeros(cfg.n_slots, np.int64)
        self.tok = np.zeros(cfg.n_slots, np.int64)
        self.slots: list[_Slot | None] = [None] * cfg.n_slots
        self.free = list(range(cfg.n_slots))
        self.slot_history = {}
        self._near_tokens = np.zeros(cfg.n_slots, np.int64)
        self._static_pinned = np.zeros(cfg.n_slots, bool)
        self._visible_clock: dict[int, float] = {}
        self.report.kv_bytes_dense_equiv = (
            arch.n_layers * cfg.n_slots * cfg.max_len
            * arch.n_kv_heads * hd * jnp.dtype(dtype).itemsize * 2)

    def _serve(self, queue: deque) -> tuple[float, int]:
        """The tick loop, until every request has retired.  Returns the
        modeled clock and the decode steps run."""
        cfg = self.cfg
        tick, clock, steps = 0, 0.0, 0
        while queue or self.pending \
                or any(s is not None for s in self.slots):
            with _span("serve.tick"):
                with _span("serve.admit"):
                    clock = self._admit_arrivals(queue, tick, clock)
                active_idx = [b for b, s in enumerate(self.slots)
                              if s is not None]
                # the chunked prefill lane: at most ``prefill_chunk_tokens``
                # of pending prompt work rides this tick, sharing the decode
                # step's weight stream instead of stalling it
                chunk_toks, completed = 0, []
                if self.pending:
                    with _span("serve.prefill"):
                        chunk_toks, completed = self._advance_prefills(
                            self._prefill_budget(len(active_idx)))
                if not active_idx and not chunk_toks and not completed:
                    if queue:             # idle fast-forward
                        tick = max(tick + 1, queue[0].arrival)
                    else:
                        tick += 1   # unreachable guard: pending implies work
                    continue

                if active_idx:
                    with _span("serve.decode"):
                        clock, q0 = self._decode_tick(active_idx,
                                                      chunk_toks, clock)
                    steps += 1
                else:
                    # prefill-only tick: the chunks stream the weights alone
                    clock += cfg.cost.prefill_cost(chunk_toks)
                for slot, job, first in completed:
                    self._complete_prefill(slot, job, first, clock)
                if active_idx and steps % cfg.tier.interval == 0:
                    # cost-aware deferral gate (the WMC queue-idle gate
                    # generalized to all four policies): while the run queue
                    # is hot — arrivals waiting or prompts still chunking —
                    # keep migration bandwidth off the critical path,
                    # bounded by ``defer_limit`` so sustained load still
                    # gets maintenance
                    hot = bool(self.pending) \
                        or bool(queue and queue[0].arrival <= tick)
                    if hot and self._defer_count < cfg.defer_limit:
                        self._defer_count += 1
                        self.report.migration_deferrals += 1
                    else:
                        self._defer_count = 0
                        with _span("serve.maintain"):
                            clock = self._maintain(q0, clock, not hot)
                tick += 1
        return clock, steps

    def _admit_arrivals(self, queue: deque, tick: int, clock: float) -> float:
        """Make this tick's arrivals visible, admit waiting requests into
        free slots, retire requests that want only the prefill token, and
        account the live KV bytes."""
        now = time.perf_counter()
        for req in queue:                  # sorted by arrival: stop early
            if req.arrival > tick:
                break
            if req.rid not in self._visible_clock:
                self._visible_clock[req.rid] = clock
                self.report.request_wall[req.rid] = [now] + [np.nan] * 3
        while queue and queue[0].arrival <= tick and self.free:
            slot = self.free.pop(0)
            if self.cfg.prefill_chunk_tokens is not None:
                self._admit_chunked(queue.popleft(), slot)
            else:
                clock = self._admit(queue.popleft(), slot, clock)
        # a request may want exactly the prefill token (max_new_tokens=1)
        for b in range(self.cfg.n_slots):
            st = self.slots[b]
            if st is not None and len(st.emitted) >= st.req.max_new_tokens:
                self._retire(b)
        self._account_kv_bytes()
        return clock

    def _decode_tick(self, active_idx: list, chunk_toks: int,
                     clock: float) -> tuple[float, jax.Array]:
        """One batched decode step over the active slots: emit a token for
        each, retire the finished.  Returns the clock and the step's layer-0
        queries (what ``_maintain`` scores with)."""
        cfg = self.cfg
        self._flush_mapping()
        pos_dev = jnp.asarray(self.pos, jnp.int32)
        tokens = {"tokens": jnp.asarray(self.tok[:, None], jnp.int32)}
        meta = self._meta(self.tier, pos_dev)
        kv_cache = {"pool_k": self.pool_k, "pool_v": self.pool_v,
                    "near_k": self.near_k, "near_v": self.near_v,
                    "pos": pos_dev}
        live = self.pos[active_idx] + 1
        t_dispatch = time.perf_counter()
        logits, new_cache, aux = self._decode(self.params, kv_cache, tokens,
                                              meta)
        self.pool_k = new_cache["pool_k"]
        self.pool_v = new_cache["pool_v"]
        self.near_k = new_cache["near_k"]
        self.near_v = new_cache["near_v"]
        if self.fused:
            # the walk's accounting (device) + an independent host shadow:
            # both must equal the live non-promoted page rows
            self.report.far_rows_touched += int(self._host(
                meta["walk_live"].sum(), "serve.wait.walk_rows"))
            self.report.far_rows_host += self._far_rows_shadow()
        else:
            # the materializing path gathers the full far view
            self.report.far_rows_touched += \
                self.n_pages * cfg.tier.page * cfg.n_slots
        self.report.far_rows_dense += \
            self.n_pages * cfg.tier.page * cfg.n_slots
        toks = self._host(jnp.argmax(logits, axis=-1),
                          "serve.wait.tokens")[:, 0]
        t_tokens = time.perf_counter()
        self.report.decode_ticks.append(
            (t_dispatch, t_tokens, len(active_idx), int(live.sum())))

        # one fused iteration: decode KV sweep + piggybacked chunk tokens
        # share the tick's weight stream
        clock += cfg.cost.decode_step_cost(
            self._near_tokens[active_idx], live, kv_shards=self.kv_shards) \
            + cfg.cost.chunk_prefill_cost(chunk_toks)
        for b in active_idx:
            st = self.slots[b]
            st.emitted.append(int(toks[b]))
            self.report.token_latencies.append(clock - st.last_emit)
            st.last_emit = clock
            self.report.request_wall[st.req.rid][3] = t_tokens
            self.report.tokens += 1
            self.pos[b] += 1
            self.tok[b] = int(toks[b])
            if len(st.emitted) >= st.req.max_new_tokens:
                self._retire(b)
        return clock, aux["q0"]


class DataParallelEngine:
    """Data-parallel serving over the mesh's 'data' axis (docs/design.md
    §2h): R engine replicas, each owning its OWN slot pool, page pool, and
    radix prefix cache, with the offline trace partitioned round-robin by
    arrival order — request i (in (arrival, rid) order) lands on replica
    i % R.  Deterministic, so replica outputs are reproducible and the
    merged report is stable across runs.

    Replicas are *modeled* as parallel: each lane accrues its own byte-cost
    clock (weights stream independently per replica — that is what data
    parallelism buys: R weight streams instead of one), and the merged
    report's ``modeled_time`` is the MAX lane clock.  Host execution is
    sequential through ONE underlying ``ServingEngine`` (its ``run`` fully
    re-initializes all mutable state, so the jitted programs compile once
    and serve every lane) — the model/host split mirrors how the byte-cost
    clock already abstracts device time everywhere else in the engine.

    Decode tokens are batching-invariant (each emitted token is pinned
    bit-identical to single-sequence ``greedy_generate``), so the merged
    ``outputs`` are bit-identical to a single-replica run of the same
    trace regardless of how admissions split across lanes
    (tests/test_mesh_serving.py)."""

    def __init__(self, params, arch: ArchConfig, cfg: ServingConfig,
                 n_replicas: int | None = None):
        if n_replicas is None:
            mesh = cfg.tier.mesh
            n_replicas = mesh.shape.get("data", 1) if mesh is not None else 1
        assert n_replicas >= 1
        self.n_replicas = int(n_replicas)
        self.engine = ServingEngine(params, arch, cfg)

    def run(self, trace: list[Request],
            scenario: str = "trace") -> ServingReport:
        R = self.n_replicas
        order = sorted(trace, key=lambda r: (r.arrival, r.rid))
        lanes = [order[i::R] for i in range(R)]
        reports = [self.engine.run(lane, scenario=scenario)
                   for lane in lanes if lane]
        if not reports:
            return ServingReport(scenario=scenario,
                                 policy=self.engine.cfg.tier.policy,
                                 n_requests=0)
        return merge_lane_reports(reports)


def sequential_baseline(params, arch: ArchConfig, trace: list[Request],
                        cfg: ServingConfig,
                        scenario: str = "trace") -> ServingReport:
    """The no-batching reference: each request served to completion by
    single-sequence ``greedy_generate`` (B=1), one after another, under the
    same modeled cost landscape (no near tier: every live KV token is
    gather-addressed at ``far_cost``)."""
    from repro.launch.serve import greedy_generate, make_decode_step
    report = ServingReport(scenario=scenario, policy="sequential",
                           n_requests=len(trace))
    step_fn = jax.jit(make_decode_step(arch))
    prefill_fn = jax.jit(
        lambda p, b: transformer.prefill(p, b, arch, max_len=cfg.max_len))
    clock = 0.0
    t0 = time.perf_counter()
    for req in sorted(trace, key=lambda r: (r.arrival, r.rid)):
        toks, _ = greedy_generate(
            params, arch, {"tokens": np.asarray(req.prompt)[None]},
            steps=req.max_new_tokens, max_len=cfg.max_len, step_fn=step_fn,
            prefill_fn=prefill_fn)
        report.outputs[req.rid] = np.asarray(toks)[0].tolist()
        S = int(req.prompt.shape[0])
        # TTFT = modeled prefill cost — the same timebase the engine uses
        # (its TTFT is queueing + prefill; the baseline models no queue).
        ttft = cfg.cost.prefill_cost(S)
        clock += ttft
        last = clock
        report.tokens += 1
        report.token_latencies.append(ttft)
        report.ttfts.append(ttft)
        report.prefill_tokens += S
        report.prefill_tokens_full += S
        for i in range(1, req.max_new_tokens):
            clock += cfg.cost.decode_step_cost(np.zeros(1),
                                               np.asarray([S + i]))
            report.token_latencies.append(clock - last)
            last = clock
            report.tokens += 1
        report.steps += req.max_new_tokens - 1
    report.wall_s = time.perf_counter() - t0
    report.modeled_time = clock
    return report
