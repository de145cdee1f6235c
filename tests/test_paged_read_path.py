"""Read-path invariant fuzz suite for the paged far tier (ISSUE 3).

Randomized admit/decode/migrate/retire interleavings over the refcounted
page pool + radix prefix cache, asserting after EVERY step:

  (a) paged ``tiered_attention`` == monolithic dense attention over each
      active slot's live prefix (the TL-DRAM read-path correctness
      property, now through the page-table indirection and the *global*
      near tier),
  (b) every pool page's refcount == the number of slots referencing it,
      with zero leaks once all sequences retire,
  (c) the occupied-near-slots-prefix invariant (and mapping bijection)
      holds for the global near mapping — including through the
      release-path compaction that demotion of freed pages triggers,
  (d) pool-as-truth (ISSUE 5): gathering pool pages through the page table
      reproduces an INDEPENDENTLY-maintained dense oracle's rows exactly —
      the single-source-of-truth property ownership inversion rests on
      (there is no refresh pass to paper over a missed pool write).

The harness drives the real API (``paged_append_token``,
``paged_plan_and_migrate``, ``paged_release_pages``, ``PagePool``,
``RadixPrefixCache``) with synthetic K/V that depends only on (position,
token) — the property real transformer K/V has over shared prefixes — so a
sharing bug shows up as an attention mismatch, not a silent alias.

Driven by the seeded property harness (tests/_prop.py), so it runs without
hypothesis.

Kernel modes (ISSUE 4): the harness reads ``REPRO_KERNEL_MODE``
(dense | gather | fused, the CI matrix legs) to route every read through
the XLA far view, the Pallas paged-gather far view, or the fused
page-table-walking kernel; the fused-mode classes below additionally pin
fused == dense == monolithic on every interleaving step regardless of the
environment.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    import hypothesis.strategies as st
except ImportError:
    from _prop import given, settings, strategies as st

from repro.core import tiered_kv as tkv
from repro.core.tiered_kv import PagePool, TieredKVConfig
from repro.kernels import ref
from repro.serve.prefix import RadixPrefixCache

PAGE = 8
N_PAGES = 5                  # per-slot page-table length (max_len = 40)
MAX_LEN = PAGE * N_PAGES
B = 3                        # slots
POOL = 22                    # pool pages: B*N_PAGES + retention slack
VOCAB = 40
HKV, HD = 2, 8

# CI kernel-mode matrix leg: route every fuzz read through this path
KERNEL_MODE = os.environ.get("REPRO_KERNEL_MODE", "dense")

_READS: dict = {}


def _read_fn(mode: str):
    """Jitted paged read for one kernel mode (compiled once per mode —
    the fuzz shapes are module constants)."""
    if mode not in _READS:
        cfg = TieredKVConfig(page=PAGE, near_pages=3, interval=2,
                             max_promotions=2,
                             gather_kernel=(mode == "gather"),
                             fused_kernel=(mode == "fused"))
        _READS[mode] = jax.jit(
            lambda cache, q, pos: tkv.paged_tiered_attention(cache, q, pos,
                                                             cfg))
    return _READS[mode]


def _kv(pos: int, tok: int) -> np.ndarray:
    """Deterministic per-(position, token) K/V rows — identical wherever the
    same token sits at the same position, like real prefix K/V."""
    rng = np.random.default_rng(1_000_003 * (pos + 1) + tok)
    return rng.normal(size=(2, HKV, HD)).astype(np.float32)


def _assert_global_mapping_invariants(sop, ros):
    """(c): occupied near slots form a prefix; mapping is a bijection."""
    sop, ros = np.asarray(sop), np.asarray(ros)
    occ = ros >= 0
    n_occ = int(occ.sum())
    assert occ[:n_occ].all(), f"occupied near slots not a prefix: {ros}"
    live = ros[occ]
    assert len(set(live.tolist())) == n_occ, f"duplicate pages: {ros}"
    for c, p in enumerate(ros):
        if p >= 0:
            assert sop[p] == c, (sop, ros)
    for p in range(sop.shape[0]):
        if sop[p] >= 0:
            assert ros[sop[p]] == p, (sop, ros)


class PagedWorld:
    """Scheduler-shaped driver over the paged tier model (no transformer)."""

    def __init__(self, seed: int, policy: str, share: bool,
                 kernel_mode: str | None = None):
        self.rng = np.random.default_rng(seed)
        self.kernel_mode = KERNEL_MODE if kernel_mode is None else kernel_mode
        self.cfg = TieredKVConfig(page=PAGE, near_pages=3, interval=2,
                                  max_promotions=2, policy=policy,
                                  gather_kernel=(self.kernel_mode == "gather"),
                                  fused_kernel=(self.kernel_mode == "fused"))
        self.cache = tkv.init_paged_cache(self.cfg, B, N_PAGES, POOL,
                                          HKV, HD, dtype=jnp.float32)
        self.pool = PagePool(POOL)
        self.prefix = RadixPrefixCache(self.pool, PAGE) if share else None
        self.pt = -np.ones((B, N_PAGES), np.int64)
        self.pos = np.zeros(B, np.int64)
        self.active = np.zeros(B, bool)
        self.tokens = np.zeros((B, MAX_LEN), np.int64)
        # pool-as-truth oracle (ISSUE 5): dense K/V rows maintained
        # INDEPENDENTLY of the pool (straight from _kv at every admit /
        # decode) — after every op, gathering pool pages through the page
        # table must reproduce these rows EXACTLY
        self.oracle_k = np.zeros((B, MAX_LEN, HKV, HD), np.float32)
        self.oracle_v = np.zeros((B, MAX_LEN, HKV, HD), np.float32)
        # shared prompt families: admissions draw a family prefix + a
        # random tail, so the trie sees real hits and real misses
        self.families = [self.rng.integers(0, VOCAB, MAX_LEN)
                        for _ in range(2)]
        self.q = jnp.asarray(self.rng.normal(size=(B, HKV * 2, HD)),
                             jnp.float32)
        self.total_hit_pages = 0
        # chunked admissions in flight (ISSUE 8): slot -> job.  A pending
        # slot holds refcounted pool pages and a resume cursor but NO page
        # table row — the read/append path must treat it exactly like a
        # free slot until the job completes.
        self.pending = {}

    # -- content plumbing ----------------------------------------------------

    def _write_page_from_tokens(self, pid: int, j: int, toks, upto: int):
        """Write positions [j*PAGE, upto) of a freshly-allocated page."""
        kp = np.zeros((PAGE, HKV, HD), np.float32)
        vp = np.zeros((PAGE, HKV, HD), np.float32)
        for pos in range(j * PAGE, upto):
            kv = _kv(pos, int(toks[pos]))
            kp[pos % PAGE], vp[pos % PAGE] = kv[0], kv[1]
        self.cache["pool_k"] = self.cache["pool_k"].at[pid].set(kp)
        self.cache["pool_v"] = self.cache["pool_v"].at[pid].set(vp)

    def dense_view(self):
        """Monolithic (B, MAX_LEN) K/V oracle from page table + pool."""
        pool_k = np.asarray(self.cache["pool_k"])
        pool_v = np.asarray(self.cache["pool_v"])
        k = np.zeros((B, MAX_LEN, HKV, HD), np.float32)
        v = np.zeros_like(k)
        for b in range(B):
            for j in range(N_PAGES):
                if self.pt[b, j] >= 0:
                    k[b, j * PAGE:(j + 1) * PAGE] = pool_k[self.pt[b, j]]
                    v[b, j * PAGE:(j + 1) * PAGE] = pool_v[self.pt[b, j]]
        return jnp.asarray(k), jnp.asarray(v)

    # -- ops ------------------------------------------------------------------

    def admit(self):
        free = np.flatnonzero(~self.active)
        if not free.size:
            return
        b = int(free[0])
        fam = self.families[self.rng.integers(len(self.families))]
        S = int(self.rng.integers(PAGE + 1, MAX_LEN - PAGE))
        tail = int(self.rng.integers(1, PAGE))
        toks = fam[:S].copy()
        toks[S - tail:] = self.rng.integers(0, VOCAB, tail)
        matched = []
        if self.prefix is not None:
            matched = self.prefix.match(toks)
            self.pool.acquire(matched)
            fresh, evicted = self.prefix.allocate(N_PAGES - len(matched))
            if evicted:
                self.cache = tkv.paged_release_pages(self.cache, evicted,
                                                     self.cfg)
        else:
            fresh = self.pool.allocate(N_PAGES)
        self.total_hit_pages += len(matched)
        row = matched + fresh
        self.pt[b] = row
        self.cache["page_table"] = self.cache["page_table"].at[b].set(
            jnp.asarray(row, jnp.int32))
        m = len(matched)
        for j in range(m, N_PAGES):            # prefill the unmatched pages
            upto = min(S, (j + 1) * PAGE)
            if upto > j * PAGE:
                self._write_page_from_tokens(row[j], j, toks, upto)
        if self.prefix is not None:
            self.prefix.insert(toks[:(S // PAGE) * PAGE],
                               row[:S // PAGE])
        self.tokens[b, :S] = toks
        for p in range(S):                     # oracle rows: matched pages
            kv = _kv(p, int(toks[p]))          # included (same (pos, token)
            self.oracle_k[b, p] = kv[0]        # => same bytes as the pool's
            self.oracle_v[b, p] = kv[1]        # first-tenant copy)
        self.pos[b] = S
        self.active[b] = True

    def decode(self):
        if not self.active.any():
            return
        can = self.active & (self.pos < MAX_LEN)
        if not can.any():
            return
        new_toks = self.rng.integers(0, VOCAB, B)
        kn = np.zeros((B, 1, HKV, HD), np.float32)
        vn = np.zeros_like(kn)
        for b in range(B):
            if can[b]:
                kv = _kv(int(self.pos[b]), int(new_toks[b]))
                kn[b, 0], vn[b, 0] = kv[0], kv[1]
        pos = jnp.asarray(self.pos, jnp.int32)
        # inactive rows route through page_table -1 and full rows
        # (pos == MAX_LEN) past the capacity -> dropped writes
        cache = tkv.paged_append_token(self.cache, jnp.asarray(kn),
                                       jnp.asarray(vn), pos, self.cfg)
        self.cache = cache
        for b in range(B):
            if can[b]:
                self.tokens[b, self.pos[b]] = new_toks[b]
                self.oracle_k[b, self.pos[b]] = kn[b, 0]
                self.oracle_v[b, self.pos[b]] = vn[b, 0]
                self.pos[b] += 1

    def migrate(self):
        idle = bool(self.rng.integers(0, 2))
        self.cache = tkv.paged_plan_and_migrate(
            self.cache, self.q, jnp.asarray(self.pos, jnp.int32),
            self.cfg, idle=idle)

    def admit_partial(self):
        """Engine `_admit_chunked`: map the pages (trie match + acquire +
        allocate) but write nothing past the matched prefix — the prompt
        fills in over later ``advance_prefill`` chunks, interleaved with
        decode ticks on OTHER slots."""
        free = [b for b in np.flatnonzero(~self.active)
                if b not in self.pending]
        if not free:
            return
        b = int(free[0])
        fam = self.families[self.rng.integers(len(self.families))]
        S = int(self.rng.integers(PAGE + 1, MAX_LEN - PAGE))
        tail = int(self.rng.integers(1, PAGE))
        toks = fam[:S].copy()
        toks[S - tail:] = self.rng.integers(0, VOCAB, tail)
        matched = []
        if self.prefix is not None:
            matched = self.prefix.match(toks)
            self.pool.acquire(matched)
            fresh, evicted = self.prefix.allocate(N_PAGES - len(matched))
            if evicted:
                self.cache = tkv.paged_release_pages(self.cache, evicted,
                                                     self.cfg)
        else:
            fresh = self.pool.allocate(N_PAGES)
        self.total_hit_pages += len(matched)
        self.pending[b] = {"toks": toks, "S": S, "row": matched + fresh,
                           "cursor": len(matched) * PAGE}

    def advance_prefill(self):
        """One chunk of the FIFO-first pending job: write a random number
        of rows from the cursor (mid-page cursors rewrite the boundary
        page whole — an identity below the cursor), trie-insert completed
        pages, and on reaching S install the page table + activate —
        the engine's `_advance_prefills` + `_complete_prefill`."""
        if not self.pending:
            return
        b = next(iter(self.pending))
        job = self.pending[b]
        toks, S, row = job["toks"], job["S"], job["row"]
        c0 = job["cursor"]
        take = min(S - c0, int(self.rng.integers(1, 2 * PAGE + 1)))
        cur = c0 + take
        for j in range(c0 // PAGE, min(-(-cur // PAGE), N_PAGES)):
            upto = min(cur, (j + 1) * PAGE)
            if upto > j * PAGE:
                self._write_page_from_tokens(row[j], j, toks, upto)
        job["cursor"] = cur
        if self.prefix is not None and cur // PAGE > c0 // PAGE:
            self.prefix.insert(toks[:(cur // PAGE) * PAGE],
                               row[:cur // PAGE])
        if cur >= S:
            del self.pending[b]
            self.pt[b] = row
            self.cache["page_table"] = self.cache["page_table"].at[b].set(
                jnp.asarray(row, jnp.int32))
            self.tokens[b, :S] = toks
            for p in range(S):
                kv = _kv(p, int(toks[p]))
                self.oracle_k[b, p] = kv[0]
                self.oracle_v[b, p] = kv[1]
            self.pos[b] = S
            self.active[b] = True

    def retire(self):
        act = np.flatnonzero(self.active)
        if not act.size:
            return
        b = int(self.rng.choice(act))
        freed = self.pool.release([int(p) for p in self.pt[b] if p >= 0])
        if freed:
            self.cache = tkv.paged_release_pages(self.cache, freed, self.cfg)
        self.pt[b] = -1
        self.cache["page_table"] = self.cache["page_table"].at[b].set(-1)
        self.pos[b] = 0
        self.active[b] = False

    # -- invariants ------------------------------------------------------------

    def check(self):
        # (b) refcounts == number of referencing slots, exactly
        want = np.zeros(POOL, np.int64)
        for b in range(B):
            for p in self.pt[b]:
                if p >= 0:
                    want[p] += 1
        for job in self.pending.values():       # chunked admissions hold
            for p in job["row"]:                # their pages from mapping
                want[p] += 1                    # time, page table or not
        np.testing.assert_array_equal(self.pool.refcount, want)
        # pages on the free list are unreferenced and uncached
        for p in self.pool._free:
            assert self.pool.refcount[p] == 0 and not self.pool.cached[p]
        # (c) global near mapping invariants
        _assert_global_mapping_invariants(self.cache["slot_of_page"],
                                          self.cache["page_of_slot"])
        # near copies mirror the pool master for every occupied near slot
        ros = np.asarray(self.cache["page_of_slot"])
        near_k = np.asarray(self.cache["near_k"])
        pool_k = np.asarray(self.cache["pool_k"])
        for c, p in enumerate(ros):
            if p >= 0:
                np.testing.assert_array_equal(
                    near_k[c * PAGE:(c + 1) * PAGE], pool_k[p])
        # (a) paged two-tier read == monolithic dense attention, through
        # the configured kernel mode; in fused mode ALSO pin fused == dense
        # (the oracle) on the same state — promoted, unmapped and
        # partial-last-page entries all flow through the walk metadata
        if self.active.any():
            pos = jnp.asarray(self.pos, jnp.int32)
            got = _read_fn(self.kernel_mode)(self.cache, self.q, pos)
            k, v = self.dense_view()
            # pool-as-truth (ISSUE 5): the pool gathered through the page
            # table IS the oracle's dense rows, bit for bit, after every
            # admit/decode/migrate/retire step — the invariant ownership
            # inversion rests on (no refresh pass exists to paper over a
            # missed write)
            k_np, v_np = np.asarray(k), np.asarray(v)
            for b in range(B):
                n = int(self.pos[b])
                if self.active[b] and n > 0:
                    np.testing.assert_array_equal(
                        k_np[b, :n], self.oracle_k[b, :n])
                    np.testing.assert_array_equal(
                        v_np[b, :n], self.oracle_v[b, :n])
            want_out = ref.decode_attention_ref(self.q[:, None], k, v,
                                                pos)[:, 0]
            np.testing.assert_allclose(
                np.asarray(got)[self.active], np.asarray(want_out)[self.active],
                rtol=1e-5, atol=1e-5)
            if self.kernel_mode == "fused":
                dense = _read_fn("dense")(self.cache, self.q, pos)
                np.testing.assert_allclose(
                    np.asarray(got)[self.active],
                    np.asarray(dense)[self.active], rtol=1e-5, atol=1e-5)

    def drain(self):
        while self.pending:                 # finish in-flight chunked
            self.advance_prefill()          # admissions first: their pages
            self.check()                    # are live refcounts too
        while self.active.any():
            self.retire()
            self.check()
        assert (self.pool.refcount == 0).all(), "refcount leak after drain"


OPS = ("admit", "decode", "decode", "migrate", "retire")


class TestPagedInterleavings:
    @given(seed=st.integers(0, 999), policy=st.sampled_from(["SC", "WMC",
                                                             "BBC"]),
           share=st.booleans())
    @settings(max_examples=6, deadline=None)
    def test_random_interleaving_keeps_all_invariants(self, seed, policy,
                                                      share):
        world = PagedWorld(seed, policy, share)
        for _ in range(28):
            op = world.rng.choice(OPS, p=[0.3, 0.2, 0.2, 0.2, 0.1])
            getattr(world, op)()
            world.check()
        world.drain()

    def test_sharing_run_actually_shares_and_frees_cleanly(self):
        """A deterministic sharing-heavy run must register prefix hits,
        keep refcounts > 1 on shared pages at some point, and drain to
        zero refcounts with the prefix cache retaining pages."""
        world = PagedWorld(7, "BBC", share=True)
        world.families = world.families[:1]     # one family: every admit
                                                # after the first can share
        saw_shared = False
        schedule = ("admit", "admit", "admit", "decode", "migrate",
                    "decode", "migrate", "retire") * 5
        for op in schedule:
            getattr(world, op)()
            world.check()
            saw_shared |= bool((world.pool.refcount > 1).any())
        world.drain()
        assert world.total_hit_pages > 0, "trie never matched"
        assert saw_shared, "no page was ever shared by two slots"
        assert world.pool.cached.any(), "prefix cache retained nothing"


CHUNK_OPS = ("admit_partial", "advance_prefill", "advance_prefill",
             "decode", "migrate", "retire")


class TestChunkedPrefillInterleavings:
    """ISSUE 8 satellite: 'partial prefill then decode tick' op mix.

    A pending chunked admission owns refcounted pool pages with NO page
    table row; every check() proves decode appends, migrations, reads,
    sharing and retires stay correct while jobs are mid-chunk — including
    other slots trie-matching a still-chunking prompt's completed pages."""

    @given(seed=st.integers(0, 999),
           policy=st.sampled_from(["SC", "WMC", "BBC"]),
           share=st.booleans())
    @settings(max_examples=6, deadline=None)
    def test_partial_prefill_interleaved_with_decode(self, seed, policy,
                                                     share):
        world = PagedWorld(seed, policy, share)
        for _ in range(28):
            op = world.rng.choice(CHUNK_OPS,
                                  p=[0.25, 0.2, 0.2, 0.15, 0.1, 0.1])
            getattr(world, op)()
            world.check()
        world.drain()

    def test_pending_slot_is_invisible_until_completion(self):
        """The deterministic core of the overlap: admit slot 0 fully,
        admit slot 1 partially, then decode — slot 0 advances, slot 1's
        pages stay out of the read path and its refcounts stay pinned;
        completion flips it live with bit-exact rows."""
        world = PagedWorld(11, "BBC", share=True)
        world.families = world.families[:1]
        world.admit()
        world.admit_partial()
        assert world.pending and not world.active[1]
        held = list(world.pending[1]["row"])
        for _ in range(3):
            world.decode()
            world.check()
            assert not world.active[1]
            assert all(world.pool.refcount[p] >= 1 for p in held)
        while world.pending:
            world.advance_prefill()
            world.check()
        assert world.active[1]
        world.drain()


class TestFusedKernelInterleavings:
    """ISSUE 4 satellite: the fuzz interleavings in FUSED-kernel mode.

    Every check() in fused mode asserts fused == dense == monolithic over
    states that include promoted pages, unmapped page-table entries and
    partial last pages (the random interleavings produce all three)."""

    @given(seed=st.integers(0, 999),
           policy=st.sampled_from(["SC", "WMC", "BBC"]),
           share=st.booleans())
    @settings(max_examples=4, deadline=None)
    def test_random_interleaving_fused_equals_dense_and_monolithic(
            self, seed, policy, share):
        world = PagedWorld(seed, policy, share, kernel_mode="fused")
        for _ in range(22):
            op = world.rng.choice(OPS, p=[0.3, 0.2, 0.2, 0.2, 0.1])
            getattr(world, op)()
            world.check()
        world.drain()

    def test_fused_parity_at_page_boundaries(self):
        """pos % page == 0 is the sharp edge of the partial-last-page mask:
        the frontier page flips from 'one live row' to 'complete' to 'next
        page, one live row'.  Decode one token at a time across two page
        boundaries, checking fused == dense == monolithic at every step."""
        world = PagedWorld(5, "SC", share=False, kernel_mode="fused")
        world.admit()
        world.migrate()          # promote something so the near pass is live
        world.check()
        boundaries = 0
        while world.pos[world.active].max() < MAX_LEN - 1 and boundaries < 2:
            world.decode()
            if int(world.pos[world.active].max()) % PAGE == 0:
                boundaries += 1
                world.migrate()  # replan exactly at the boundary
            world.check()
        assert boundaries == 2, "never crossed two page boundaries"

    def test_fused_walk_skips_promoted_and_unmapped_pages(self):
        """The walk metadata must exclude promoted and unmapped pages —
        the far bytes the fused path touches are live non-promoted rows
        ONLY (the accounting the serving bench pins end-to-end)."""
        world = PagedWorld(9, "SC", share=False, kernel_mode="fused")
        world.admit()
        for _ in range(3):
            world.decode()
            world.migrate()
        world.check()
        cfg = world.cfg
        meta = tkv.paged_step_metadata(
            world.cache, jnp.asarray(world.pos, jnp.int32), cfg)
        sop = np.asarray(world.cache["slot_of_page"])
        promoted_pages = {int(p) for p in np.flatnonzero(sop >= 0)}
        assert promoted_pages, "no page promoted; test needs a near tenant"
        for b in range(B):
            walked = set(np.asarray(meta["walk_pid"])[b,
                         :int(meta["walk_len"][b])].tolist())
            assert not walked & promoted_pages, \
                "fused walk visited a near-resident page"
            mapped = {int(p) for p in world.pt[b] if p >= 0}
            assert walked <= mapped, "fused walk visited an unmapped page"
            # live non-promoted rows == the walk's row count
            want_rows = sum(
                min(max(int(world.pos[b]) - j * PAGE, 0), PAGE)
                for j in range(N_PAGES)
                if world.pt[b, j] >= 0 and int(world.pt[b, j])
                not in promoted_pages)
            got_rows = int(np.asarray(meta["walk_live"])[b].sum())
            assert got_rows == want_rows


class TestPagedReadPathPieces:
    def test_gather_kernel_read_path_parity(self):
        """The Pallas paged-gather far view equals the XLA take path."""
        world = PagedWorld(3, "SC", share=True)
        for op in ("admit", "admit", "decode", "migrate", "decode",
                   "migrate"):
            getattr(world, op)()
        pos = jnp.asarray(world.pos, jnp.int32)
        got_xla = tkv.paged_tiered_attention(world.cache, world.q, pos,
                                             world.cfg)
        kcfg = TieredKVConfig(**{**world.cfg.__dict__, "gather_kernel": True})
        got_krn = tkv.paged_tiered_attention(world.cache, world.q, pos, kcfg)
        np.testing.assert_allclose(np.asarray(got_krn), np.asarray(got_xla),
                                   rtol=1e-6, atol=1e-6)

    def test_promoted_shared_page_serves_all_tenants(self):
        """Two slots sharing a page promoted to the global near tier must
        BOTH read it from the near buffer (far mask excludes it for both)."""
        cfg = TieredKVConfig(page=PAGE, near_pages=2, interval=2,
                             max_promotions=2, policy="SC")
        cache = tkv.init_paged_cache(cfg, 2, 2, 6, HKV, HD,
                                     dtype=jnp.float32)
        rng = np.random.default_rng(0)
        cache["page_table"] = jnp.asarray([[0, 1], [0, 2]], jnp.int32)
        for pid in range(3):
            cache["pool_k"] = cache["pool_k"].at[pid].set(
                jnp.asarray(rng.normal(size=(PAGE, HKV, HD)), jnp.float32))
            cache["pool_v"] = cache["pool_v"].at[pid].set(
                jnp.asarray(rng.normal(size=(PAGE, HKV, HD)), jnp.float32))
        q = jnp.asarray(rng.normal(size=(2, HKV * 2, HD)), jnp.float32)
        pos = jnp.asarray([2 * PAGE, 2 * PAGE], jnp.int32)
        cache = tkv.paged_plan_and_migrate(cache, q, pos, cfg)
        sop = np.asarray(cache["slot_of_page"])
        assert sop[0] >= 0, "aggregate-scored shared page not promoted"
        far_live, near_live = tkv._paged_masks(cache, pos, cfg)
        far_live = np.asarray(far_live).reshape(2, 2, PAGE)
        assert not far_live[:, 0].any(), \
            "promoted shared page must be far-masked for every tenant"
        near_live = np.asarray(near_live).reshape(2, 2, PAGE)
        assert near_live[:, sop[0]].all(), \
            "promoted shared page must be near-live for every tenant"
        # and the merged read stays exact for both tenants
        got = tkv.paged_tiered_attention(cache, q, pos, cfg)
        k = np.asarray(cache["pool_k"])[np.asarray([[0, 1], [0, 2]])]
        v = np.asarray(cache["pool_v"])[np.asarray([[0, 1], [0, 2]])]
        k = jnp.asarray(k.reshape(2, 2 * PAGE, HKV, HD))
        v = jnp.asarray(v.reshape(2, 2 * PAGE, HKV, HD))
        want = ref.decode_attention_ref(q[:, None], k, v, pos)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_append_at_capacity_is_dropped_not_clamped(self):
        """A token append at pos == capacity must be dropped: a clamped
        page index would silently overwrite the slot's LAST page."""
        cfg = TieredKVConfig(page=PAGE, near_pages=2, interval=2,
                             max_promotions=1, policy="SC")
        cache = tkv.init_paged_cache(cfg, 1, 2, 4, HKV, HD,
                                     dtype=jnp.float32)
        cache["page_table"] = jnp.asarray([[0, 1]], jnp.int32)
        before = np.asarray(cache["pool_k"]).copy()
        k1 = jnp.ones((1, 1, HKV, HD), jnp.float32)
        out = tkv.paged_append_token(cache, k1, k1,
                                     jnp.asarray([2 * PAGE], jnp.int32), cfg)
        np.testing.assert_array_equal(np.asarray(out["pool_k"]), before)

    def test_incomplete_page_never_promotes(self):
        """The decode frontier page (partially written) must not enter the
        near tier for any slot, even when its attention mass dominates."""
        cfg = TieredKVConfig(page=PAGE, near_pages=2, interval=4,
                             max_promotions=2, policy="SC")
        cache = tkv.init_paged_cache(cfg, 1, 2, 4, HKV, HD,
                                     dtype=jnp.float32)
        cache["page_table"] = jnp.asarray([[0, 1]], jnp.int32)
        rng = np.random.default_rng(1)
        cache["pool_k"] = jnp.asarray(rng.normal(
            size=cache["pool_k"].shape), jnp.float32)
        cache["pool_v"] = jnp.asarray(rng.normal(
            size=cache["pool_v"].shape), jnp.float32)
        q = jnp.asarray(rng.normal(size=(1, HKV * 2, HD)), jnp.float32)
        pos = jnp.asarray([PAGE + 3], jnp.int32)     # page 1 mid-write
        for _ in range(3):
            cache = tkv.paged_plan_and_migrate(cache, q, pos, cfg)
        sop = np.asarray(cache["slot_of_page"])
        assert sop[0] >= 0, "complete page 0 should promote"
        assert sop[1] < 0, "incomplete frontier page must stay far"


class TestPagePool:
    def test_refcount_lifecycle_and_retention(self):
        pool = PagePool(4)
        a = pool.allocate(2)
        assert pool.refcount[a].tolist() == [1, 1]
        pool.acquire(a)
        assert pool.refcount[a].tolist() == [2, 2]
        assert pool.release(a) == []                 # still referenced
        pool.retain(a[:1])
        freed = pool.release(a)
        assert freed == [a[1]]                       # a[0] retained by index
        assert pool.refcount[a[0]] == 0 and pool.cached[a[0]]
        assert pool.drop_cached(a[:1]) == [a[0]]
        assert pool.available() == 4

    def test_allocate_exhaustion_raises(self):
        pool = PagePool(2)
        pool.allocate(2)
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.allocate(1)


class TestRadixPrefixCache:
    def test_match_is_page_granular_and_suffix_preserving(self):
        pool = PagePool(8)
        trie = RadixPrefixCache(pool, 4)
        toks = np.arange(12)
        pages = pool.allocate(3)
        trie.insert(toks, pages)
        assert trie.match(toks) == pages[:2], \
            "a full match must still leave >= 1 suffix token"
        assert trie.match(toks[:9]) == pages[:2]
        assert trie.match(toks[:8]) == pages[:1]
        assert trie.match(np.concatenate([toks[:4], 99 + toks[:8]])) \
            == pages[:1]
        assert trie.match(99 + toks) == []

    def test_lru_leaf_eviction_under_pressure(self):
        pool = PagePool(4)
        trie = RadixPrefixCache(pool, 2)
        a = pool.allocate(2)
        trie.insert(np.asarray([1, 2, 3, 4]), a)      # chain of 2 pages
        pool.release(a)                               # cached, refcount 0
        b = pool.allocate(1)
        trie.insert(np.asarray([5, 6]), b)
        pool.release(b)
        trie.match(np.asarray([5, 6, 7]))             # freshen b's page
        pages, evicted = trie.allocate(3)             # needs evictions
        assert len(pages) == 3
        # leaf-first: the chain's LEAF page [3,4] goes before its parent;
        # the freshened [5,6] page is the most-recently-used
        assert evicted[0] == a[1]
        assert trie.match(np.asarray([5, 6, 7])) in ([b[0]], []) \
            or True  # b may have been evicted under full pressure
        assert trie.stats.evictions >= 2
