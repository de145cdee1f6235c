"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

try:                                   # optional fast path: real hypothesis
    from hypothesis import given, settings
    import hypothesis.strategies as st
except ImportError:                    # seeded fallback harness (tests/_prop)
    from _prop import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.paged_attention import _head_pair, paged_attention
from repro.kernels.paged_gather import paged_gather
from repro.kernels.ssd_scan import ssd_chunk_scan
from repro.kernels.tiered_attention import near_decode_attention
from repro.kernels.tiered_gather import tiered_gather


def _rand(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.5).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,H,Hkv,hd,bq,bkv", [
        (1, 128, 4, 4, 64, 64, 64),      # MHA
        (2, 256, 8, 2, 64, 128, 128),    # GQA 4:1
        (1, 128, 4, 1, 128, 128, 64),    # MQA, wide head
        (2, 64, 2, 2, 32, 32, 32),       # tiny
    ])
    def test_against_ref(self, dtype, B, S, H, Hkv, hd, bq, bkv):
        ks = jax.random.split(jax.random.key(0), 3)
        q = _rand(ks[0], (B, S, H, hd), dtype)
        k = _rand(ks[1], (B, S, Hkv, hd), dtype)
        v = _rand(ks[2], (B, S, Hkv, hd), dtype)
        got = flash_attention_fwd(q, k, v, causal=True, block_q=bq,
                                  block_kv=bkv, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_sliding_window(self):
        ks = jax.random.split(jax.random.key(1), 3)
        B, S, H, hd, W = 1, 256, 2, 32, 64
        q = _rand(ks[0], (B, S, H, hd), jnp.float32)
        k = _rand(ks[1], (B, S, H, hd), jnp.float32)
        v = _rand(ks[2], (B, S, H, hd), jnp.float32)
        got = flash_attention_fwd(q, k, v, causal=True, window=W,
                                  block_q=64, block_kv=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=W)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_matches_model_layer(self):
        """The model's scan formulation and the kernel agree."""
        from repro.models.layers import flash_attention as model_flash
        ks = jax.random.split(jax.random.key(2), 3)
        B, S, H, hd = 2, 128, 4, 32
        q = _rand(ks[0], (B, S, H, hd), jnp.float32)
        k = _rand(ks[1], (B, S, H, hd), jnp.float32)
        v = _rand(ks[2], (B, S, H, hd), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        a = model_flash(q, k, v, pos, pos, causal=True, kv_chunk=64)
        b = flash_attention_fwd(q, k, v, causal=True, block_q=64,
                                block_kv=64, interpret=True)
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


class TestNearDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,Hkv,hd,T", [
        (2, 4, 2, 64, 256),
        (1, 8, 8, 32, 128),
        (3, 6, 2, 128, 128),
    ])
    def test_stats_against_ref(self, dtype, B, H, Hkv, hd, T):
        ks = jax.random.split(jax.random.key(3), 4)
        q = _rand(ks[0], (B, H, hd), dtype)
        k = _rand(ks[1], (B, T, Hkv, hd), dtype)
        v = _rand(ks[2], (B, T, Hkv, hd), dtype)
        length = jax.random.randint(ks[3], (B,), 1, T + 1)
        out, m, l = near_decode_attention(q, k, v, length, block_kv=64,
                                          interpret=True)
        want_out, want_m, want_l = ref.decode_attention_stats_ref(
            q[:, None], k, v, length)
        np.testing.assert_allclose(np.asarray(m), np.asarray(want_m),
                                   **TOL[dtype])
        np.testing.assert_allclose(np.asarray(l), np.asarray(want_l),
                                   rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4)
        # compare normalized outputs (unnormalized scale is implementation-defined)
        np.testing.assert_allclose(
            np.asarray(out / np.maximum(np.asarray(l)[..., None], 1e-30)),
            np.asarray(want_out / np.maximum(np.asarray(want_l)[..., None],
                                             1e-30)),
            **TOL[dtype])

    def test_two_tier_merge_equals_monolithic(self):
        """Near+far tiers with LSE merge == attention over the concatenation —
        the correctness property of the TL-DRAM read path."""
        ks = jax.random.split(jax.random.key(4), 5)
        B, H, Hkv, hd, Tn, Tf = 2, 4, 2, 32, 128, 192
        q = _rand(ks[0], (B, H, hd), jnp.float32)
        kn = _rand(ks[1], (B, Tn, Hkv, hd), jnp.float32)
        vn = _rand(ks[2], (B, Tn, Hkv, hd), jnp.float32)
        kf = _rand(ks[3], (B, Tf, Hkv, hd), jnp.float32)
        vf = _rand(ks[4], (B, Tf, Hkv, hd), jnp.float32)
        n_len = jnp.array([128, 64], jnp.int32)
        f_len = jnp.array([192, 100], jnp.int32)

        got = ops.tiered_decode_attention(q, kn, vn, n_len, kf, vf, f_len,
                                          block_kv=64)

        # monolithic: concatenate live prefixes per batch element
        outs = []
        for b in range(B):
            kcat = jnp.concatenate([kn[b, :n_len[b]], kf[b, :f_len[b]]])[None]
            vcat = jnp.concatenate([vn[b, :n_len[b]], vf[b, :f_len[b]]])[None]
            o = ref.decode_attention_ref(
                q[b:b + 1, None], kcat, vcat,
                jnp.array([kcat.shape[1]], jnp.int32))
            outs.append(o[0, 0])
        want = jnp.stack(outs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def _walk_meta(key, B, P, page, n_pages, C, lens=None):
    """Random but well-formed walk/near metadata for the fused kernel:
    distinct pool ids per slot, live counts in [1, page] (partial last
    pages included), near live counts in [0, page] (0 = non-tenant).
    ``lens``: the slots' walk lengths (random in [0, n_pages] if None)."""
    ks = jax.random.split(key, 4)
    walk_len = (jax.random.randint(ks[0], (B,), 0, n_pages + 1)
                if lens is None else jnp.asarray(lens))
    pid = jnp.stack([jax.random.permutation(k, P)[:n_pages]
                     for k in jax.random.split(ks[1], B)]).astype(jnp.int32)
    walk_live = jax.random.randint(ks[2], (B, n_pages), 1, page + 1)
    near_live = jax.random.randint(ks[3], (B, C), 0, page + 1)
    return pid, walk_live, walk_len.astype(jnp.int32), near_live


class TestPagedAttention:
    """Fused page-table-walking decode kernel vs its jnp oracle (ISSUE 4)."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,Hkv,hd,page,n_pages,C,P,lens", [
        pytest.param(2, 4, 2, 64, 16, 4, 2, 12, None,       # GQA
                     id="2-4-2-64-16-4-2-12"),
        pytest.param(1, 8, 8, 32, 8, 6, 3, 10, None,        # MHA, W > C
                     id="1-8-8-32-8-6-3-10"),
        pytest.param(3, 6, 2, 128, 32, 2, 4, 8, None,       # near > far
                     id="3-6-2-128-32-2-4-8"),
        pytest.param(2, 2, 1, 16, 8, 5, 1, 16, None,        # MQA, tiny
                     id="2-2-1-16-8-5-1-16"),
        # walks across the block edges: kb = 128 // page far pages a
        # block, W = n_pages not a multiple of kb; lens 0, 1, kb - 1,
        # kb, kb + 1, 2 kb + 3
        pytest.param(6, 4, 2, 64, 32, 13, 2, 80, (0, 1, 3, 4, 5, 11),
                     id="block-edges-kb4"),
        pytest.param(6, 32, 4, 128, 16, 21, 3, 128, (0, 1, 7, 8, 9, 19),
                     id="block-edges-kb8-yi-gqa"),          # Hkv 4, g 8
        pytest.param(6, 16, 8, 128, 16, 21, 3, 128, (0, 1, 7, 8, 9, 19),
                     id="block-edges-kb8-qwen-gqa"),        # Hkv 8, g 2
    ])
    def test_against_ref(self, dtype, B, H, Hkv, hd, page, n_pages, C, P,
                         lens):
        ks = jax.random.split(jax.random.key(11), 6)
        q = _rand(ks[0], (B, H, hd), dtype)
        pool_k = _rand(ks[1], (P, page, Hkv, hd), dtype)
        pool_v = _rand(ks[2], (P, page, Hkv, hd), dtype)
        near_k = _rand(ks[3], (C * page, Hkv, hd), dtype)
        near_v = _rand(ks[4], (C * page, Hkv, hd), dtype)
        pid, walk_live, walk_len, near_live = _walk_meta(
            ks[5], B, P, page, n_pages, C, lens)
        got = paged_attention(q, pool_k, pool_v, near_k, near_v, pid,
                              walk_live, walk_len, near_live, interpret=True)
        want = ref.paged_attention_ref(q, pool_k, pool_v, near_k, near_v,
                                       pid, walk_live, walk_len, near_live)
        # compare m, then normalized outputs (unnormalized scale is
        # implementation-defined between the two accumulation orders)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   **TOL[dtype])
        g_out = np.asarray(got[0]) / np.maximum(np.asarray(got[2])[..., None],
                                                1e-30)
        w_out = np.asarray(want[0]) / np.maximum(
            np.asarray(want[2])[..., None], 1e-30)
        np.testing.assert_allclose(g_out, w_out, **TOL[dtype])

    def test_empty_walk_and_dead_near_yield_zero_mass(self):
        """A slot with nothing live (walk_len 0, all near_live 0) must
        produce l == 0 — the LSE merge then yields zeros, not NaNs."""
        B, H, Hkv, hd, page, C, P = 1, 2, 1, 16, 8, 2, 4
        ks = jax.random.split(jax.random.key(12), 5)
        q = _rand(ks[0], (B, H, hd), jnp.float32)
        pool = _rand(ks[1], (P, page, Hkv, hd), jnp.float32)
        near = _rand(ks[2], (C * page, Hkv, hd), jnp.float32)
        zeros2 = jnp.zeros((B, 3), jnp.int32)
        out, m, l = paged_attention(
            q, pool, pool, near, near, zeros2, zeros2,
            jnp.zeros((B,), jnp.int32), jnp.zeros((B, C), jnp.int32),
            interpret=True)
        assert np.all(np.asarray(l) == 0.0)
        assert np.all(np.asarray(out) == 0.0)
        merged = ref.merge_attention_stats([(out, m, l)])
        assert np.isfinite(np.asarray(merged)).all()

    @pytest.mark.parametrize("H,Hkv,hd", [(16, 8, 128), (32, 4, 128)],
                             ids=["qwen-gqa", "yi-gqa"])
    def test_poisoned_pool_stays_finite(self, H, Hkv, hd):
        """Every byte the walk must not read is NaN: the pool pages on no
        slot's walk (padding entries past ``walk_len`` point at them), the
        dead rows past each walked page's live count, the near rows dead
        for every slot, and (TPU interpret mode) all uninitialised VMEM,
        so the block buffers' stale rows are NaN too.  A copy of the wrong
        page, or a dead row reaching the scores or the p·v sum, turns the
        output NaN; the double-buffered copies must not race the reads."""
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call
        B, page, C, W, P = 6, 16, 3, 21, 160
        lens = np.array([0, 1, 7, 8, 9, 19], np.int32)     # kb = 8
        ks = jax.random.split(jax.random.key(13), 6)
        q = _rand(ks[0], (B, H, hd), jnp.float32)
        pool_k = np.array(_rand(ks[1], (P, page, Hkv, hd), jnp.float32))
        pool_v = np.array(_rand(ks[2], (P, page, Hkv, hd), jnp.float32))
        near_k = np.array(_rand(ks[3], (C * page, Hkv, hd), jnp.float32))
        near_v = np.array(_rand(ks[4], (C * page, Hkv, hd), jnp.float32))
        rng = np.random.default_rng(13)
        own = rng.permutation(P)              # disjoint pages per slot
        pid = np.zeros((B, W), np.int32)
        walk_live = np.zeros((B, W), np.int32)
        walked = own[:lens.sum()]
        pid[:] = own[-1]                      # padding -> a poisoned page
        for b, (lo, n) in enumerate(zip(np.cumsum(lens) - lens, lens)):
            pid[b, :n] = own[lo:lo + n]
            walk_live[b, :n] = rng.integers(1, page + 1, n)
        near_live = rng.integers(0, page + 1, (B, C)).astype(np.int32)
        args = (pid, walk_live, lens, near_live)
        want = ref.paged_attention_ref(q, pool_k, pool_v, near_k, near_v,
                                       *args)

        bad = np.ones(P, bool)
        bad[walked] = False
        for arr in (pool_k, pool_v):
            arr[bad] = np.nan
            for b in range(B):
                for i in range(lens[b]):
                    arr[pid[b, i], walk_live[b, i]:] = np.nan
        for arr in (near_k, near_v):
            for c in range(C):
                arr[c * page + near_live[:, c].max():(c + 1) * page] = \
                    np.nan
        got = paged_attention(
            q, pool_k, pool_v, near_k, near_v, *args,
            interpret=pltpu.InterpretParams(uninitialized_memory="nan",
                                            detect_races=True))
        jax.block_until_ready(got)      # the race record is written as it runs
        assert not interpret_pallas_call.races.races_found
        for a in got:
            assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=2e-5, atol=2e-5)
        g = np.asarray(got[0]) / np.maximum(np.asarray(got[2])[..., None],
                                            1e-30)
        w = np.asarray(want[0]) / np.maximum(np.asarray(want[2])[..., None],
                                             1e-30)
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("Hkv", [2, 4, 8])
    def test_head_pair_reads_each_head_exactly(self, Hkv, masked):
        """The packed bf16 read returns head h's rows bit for bit, rows
        start.. of the ref, and zero in exactly the rows its masks drop.
        Every sign, mantissa and low exponent bit is random (the top
        exponent bit is cleared: the CPU quiets signalling NaNs)."""
        N, start, n, hd = 64, 16, 32, 128
        ks = jax.random.split(jax.random.key(18), 2)
        x = jax.lax.bitcast_convert_type(
            jax.random.bits(ks[0], (N, Hkv, hd), jnp.uint16)
            & jnp.uint16(0xBFFF), jnp.bfloat16)
        alive = jax.random.bernoulli(ks[1], 0.7, (n,))

        def kernel(x_ref, alive_ref, o_ref):
            keep = None
            if masked:
                a = jnp.broadcast_to(alive_ref[...], (n // 2, 2, hd))
                keep = (a[:, 0] != 0, a[:, 1] != 0)
            for s in range(Hkv // 2):
                o_ref[2 * s], o_ref[2 * s + 1] = _head_pair(x_ref, start, n,
                                                            s, keep)

        got = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((Hkv, n, hd),
                                                   jnp.bfloat16),
            interpret=True)(x, alive.astype(jnp.int32).reshape(n // 2, 2, 1))
        want = jnp.swapaxes(x[start:start + n], 0, 1)
        if masked:
            want = jnp.where(alive[None, :, None], want, jnp.bfloat16(0))
        bits = lambda a: np.asarray(jax.lax.bitcast_convert_type(a,
                                                                 jnp.uint16))
        np.testing.assert_array_equal(bits(got), bits(want))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("H,Hkv", [(16, 8), (32, 4)],
                             ids=["qwen-gqa", "yi-gqa"])
    def test_head_slice_is_bit_identical(self, H, Hkv, dtype):
        """No arithmetic crosses KV heads: the kernel run on the first
        Hkv/2 KV heads (and their query heads) returns exactly that slice
        of the full call's out, m and l — what a KV-head-sharded mesh
        relies on to gather stats bit-identical to one device."""
        B, hd, page, n_pages, C, P = 4, 128, 16, 11, 2, 48
        ks = jax.random.split(jax.random.key(14), 6)
        q = _rand(ks[0], (B, H, hd), dtype)
        pool_k = _rand(ks[1], (P, page, Hkv, hd), dtype)
        pool_v = _rand(ks[2], (P, page, Hkv, hd), dtype)
        near_k = _rand(ks[3], (C * page, Hkv, hd), dtype)
        near_v = _rand(ks[4], (C * page, Hkv, hd), dtype)
        walk = _walk_meta(ks[5], B, P, page, n_pages, C, (0, 3, 8, 11))
        full = paged_attention(q, pool_k, pool_v, near_k, near_v, *walk,
                               interpret=True)
        hl = Hkv // 2
        part = paged_attention(q[:, :H // 2], pool_k[..., :hl, :],
                               pool_v[..., :hl, :], near_k[:, :hl],
                               near_v[:, :hl], *walk, interpret=True)
        for got, want in zip(part, full):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want)[:, :H // 2])

    @given(seed=st.integers(0, 99))
    @settings(max_examples=10, deadline=None)
    def test_property_random_meta(self, seed):
        B, H, Hkv, hd, page, n_pages, C, P = 2, 4, 2, 16, 8, 3, 2, 9
        ks = jax.random.split(jax.random.key(seed), 6)
        q = _rand(ks[0], (B, H, hd), jnp.float32)
        pool_k = _rand(ks[1], (P, page, Hkv, hd), jnp.float32)
        pool_v = _rand(ks[2], (P, page, Hkv, hd), jnp.float32)
        near_k = _rand(ks[3], (C * page, Hkv, hd), jnp.float32)
        near_v = _rand(ks[4], (C * page, Hkv, hd), jnp.float32)
        pid, walk_live, walk_len, near_live = _walk_meta(
            ks[5], B, P, page, n_pages, C)
        got = paged_attention(q, pool_k, pool_v, near_k, near_v, pid,
                              walk_live, walk_len, near_live, interpret=True)
        want = ref.paged_attention_ref(q, pool_k, pool_v, near_k, near_v,
                                       pid, walk_live, walk_len, near_live)
        g = np.asarray(got[0]) / np.maximum(np.asarray(got[2])[..., None],
                                            1e-30)
        w = np.asarray(want[0]) / np.maximum(np.asarray(want[2])[..., None],
                                             1e-30)
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


class TestPagedGatherBudget:
    def test_pool_over_vmem_budget_raises(self):
        """ISSUE 4 satellite: the whole-pool-in-VMEM BlockSpec must refuse
        oversized pools with a clear error, not a silent docstring caveat."""
        pool = jnp.zeros((8, 16, 2, 16), jnp.float32)
        ids = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="VMEM.*budget|budget"):
            paged_gather(pool, ids, interpret=True,
                         vmem_budget_bytes=pool.nbytes - 1)

    def test_pool_within_budget_runs(self):
        pool = jnp.arange(8 * 16 * 2 * 16, dtype=jnp.float32
                          ).reshape(8, 16, 2, 16)
        ids = jnp.asarray([[3, -1]], jnp.int32)
        got = paged_gather(pool, ids, interpret=True,
                           vmem_budget_bytes=pool.nbytes)
        want = ref.paged_gather_ref(pool, ids)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestTieredGather:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("T,C,D,bt", [(64, 16, 32, 32), (100, 8, 64, 64),
                                          (256, 128, 16, 128)])
    def test_against_ref(self, dtype, T, C, D, bt):
        ks = jax.random.split(jax.random.key(5), 3)
        near = _rand(ks[0], (C, D), dtype)
        far = _rand(ks[1], (T, D), dtype)
        slots = jax.random.randint(ks[2], (T,), -1, C)
        got = tiered_gather(near, slots, far, block_t=bt, interpret=True)
        want = ref.tiered_gather_ref(near, slots, far)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=0, atol=0)

    @given(t=st.integers(8, 96), c=st.integers(1, 32), seed=st.integers(0, 99))
    @settings(max_examples=20, deadline=None)
    def test_property_random_shapes(self, t, c, seed):
        ks = jax.random.split(jax.random.key(seed), 3)
        D = 8
        near = _rand(ks[0], (c, D), jnp.float32)
        far = _rand(ks[1], (t, D), jnp.float32)
        slots = jax.random.randint(ks[2], (t,), -1, c)
        got = tiered_gather(near, slots, far, block_t=32, interpret=True)
        want = ref.tiered_gather_ref(near, slots, far)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestSSDScan:
    @pytest.mark.parametrize("B,nc,H,P,N,bh", [
        (1, 4, 8, 16, 32, 4), (2, 8, 4, 8, 16, 4), (1, 16, 16, 32, 16, 8)])
    def test_against_ref(self, B, nc, H, P, N, bh):
        ks = jax.random.split(jax.random.key(6), 3)
        states = _rand(ks[0], (B, nc, H, P, N), jnp.float32)
        decays = jax.nn.sigmoid(_rand(ks[1], (B, nc, H), jnp.float32))
        h0 = _rand(ks[2], (B, H, P, N), jnp.float32)
        hp, hf = ssd_chunk_scan(states, decays, h0, block_h=bh, interpret=True)
        want_hp, want_hf = jax.vmap(ref.ssd_chunk_scan_ref)(states, decays, h0)
        np.testing.assert_allclose(np.asarray(hp), np.asarray(want_hp),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(want_hf),
                                   rtol=1e-6, atol=1e-6)
