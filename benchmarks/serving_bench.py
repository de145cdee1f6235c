"""Serving-engine scenario suite (the serving twin of the paper's Fig 8).

Six arrival scenarios x four tier policies through the continuous-batching
engine (`repro.serve`) — the matrix runs the engine the way it is meant to
be deployed since ISSUE 8: prefix cache ON, chunked admission prefill ON,
migration overlapped — reporting per cell:

  tokens/s (wall)       : aggregate decode throughput, post-compile.
  tokens/kcost          : modeled-byte-cost throughput (near pages streamed,
                          far pages gather-derated, IST billed — TierCosts).
  near-tier hit mass    : attention mass served by the near tier (the
                          paper's near-segment hit rate analogue).
  p50 / p99 latency     : modeled per-token latency (inter-token gaps;
                          first token includes queueing + prefill).

Plus two acceptance cells:

  continuous_vs_sequential : on steady Zipfian the engine must sustain
      >= 2x the aggregate tokens/s of single-sequence ``greedy_generate``
      serving, token-identical to that reference.  Since ISSUE 4 the
      baseline's TTFT is its modeled prefill cost (the engine's timebase),
      so the engine/sequential p50 TTFT columns are finally comparable.
  prefix_sharing : on the shared-system-prompt trace the radix prefix
      cache (``repro.serve.prefix``) must cut prefilled tokens >= 40% and
      improve modeled p50 TTFT vs the non-sharing engine, with emitted
      tokens bit-identical (ISSUE 3 acceptance).
  fused_kernel : dense vs fused read path on steady Zipfian (ISSUE 4
      acceptance): emitted tokens bit-identical, and the fused path's far
      rows touched == the sum of live non-promoted page rows (device walk
      accounting == independent host shadow), never ``n_pages*page*B``.
  pool_native : pool-as-single-source-of-truth memory (ISSUE 5
      acceptance): peak live KV bytes (referenced pool pages; near-tier
      copies are derived duplicates, reported separately) <= 0.6x the
      dense-equivalent per-slot master on the shared_system_prompt and
      long_context_summarize traces, with zero orphaned pages (the
      engine's shutdown refcount sweep runs inside every cell).
  mesh_scaling : ISSUE 10 acceptance — 4 data-parallel engine lanes
      (round-robin admissions, per-lane byte-cost clocks, fleet clock =
      slowest lane) must deliver >= 3x the single-lane modeled tokens/cost
      on the slot-bound steady Zipfian, tokens bit-identical; the
      per-device throughput column is regression-gated.
  chunked_prefill : ISSUE 8 acceptance — budgeted chunked admission
      prefill + overlapped migration vs the synchronous engine on the
      two stall-dominated traces (bursty, long_context_stragglers):
      emitted tokens bit-identical, and modeled p99 token latency AND
      p50 TTFT both improve >= 25% (the long-prompt admission stall no
      longer lands inside in-flight requests' inter-token gaps).

``run_all`` also emits **BENCH_serving.json** (tokens/s, p50/p99 latency,
TTFT, far-rows-touched, live-KV-bytes per cell) so the bench trajectory
has data points — `benchmarks/check_bench_regression.py` diffs a fresh run
against the committed file in CI.

  PYTHONPATH=src python -m benchmarks.serving_bench
"""

from __future__ import annotations

import json

import jax

from repro.configs.registry import ARCHS
from repro.core.tiered_kv import TieredKVConfig
from repro.models import transformer
from repro.serve import (DataParallelEngine, ServingConfig, ServingEngine,
                         ServingReport, sequential_baseline)
from repro.serve.trace import SCENARIOS

POLICIES = ("SC", "WMC", "BBC", "STATIC")


def _setup(arch_name="qwen3-1.7b", seed=0):
    arch = ARCHS[arch_name].reduced()
    params = transformer.init_params(jax.random.key(seed), arch)
    return arch, params


def _config(policy: str, n_slots=6, max_len=128, page=16, near_pages=2,
            interval=4, share=False, fused=False, chunk=None,
            overlap=False) -> ServingConfig:
    tier = TieredKVConfig(page=page, near_pages=near_pages,
                          interval=interval, policy=policy,
                          fused_kernel=fused)
    return ServingConfig(n_slots=n_slots, max_len=max_len,
                         prefill_bucket=16, tier=tier, share_prefix=share,
                         prefill_chunk_tokens=chunk,
                         overlap_migration=overlap)


# The matrix's deployment config (ISSUE 8): radix prefix cache on (the
# shared-prefix scenarios must show non-zero hit rates — the old matrix ran
# share=False and pinned a 0.0 column for every cell), chunked admission
# prefill, migration on the background lane.
MATRIX_CHUNK = 96


def _matrix_config(policy: str, fused=False) -> ServingConfig:
    return _config(policy, share=True, fused=fused, chunk=MATRIX_CHUNK,
                   overlap=True)


def _traces(vocab: int):
    return {
        "steady_zipfian": SCENARIOS["steady_zipfian"](
            vocab, n_requests=12, prompt_len=24, max_new_tokens=16, gap=1),
        "bursty": SCENARIOS["bursty"](
            vocab, n_requests=12, prompt_len=24, max_new_tokens=16,
            burst=4, burst_gap=16),
        # gap=0 floods the queue (the post-ISSUE-8 trace fix): every
        # request arrives at once, so the median request waits behind the
        # stragglers' full-prompt prefills — the regime the chunked lane
        # exists for.  The old gap=2 let every arrival find a free slot
        # and its own prefill was the whole TTFT, hiding the admission
        # stall from the p50 columns entirely.
        "long_context_stragglers": SCENARIOS["long_context_stragglers"](
            vocab, n_requests=10, prompt_len=16, max_new_tokens=12,
            straggler_every=4, long_factor=4, gap=0),
        "shifting_hotspot": SCENARIOS["shifting_hotspot"](
            vocab, n_requests=12, prompt_len=24, max_new_tokens=16, gap=1),
        "shared_system_prompt": SCENARIOS["shared_system_prompt"](
            vocab, n_requests=12, sys_len=64, user_len=16,
            max_new_tokens=12, gap=2),
        "long_context_summarize": SCENARIOS["long_context_summarize"](
            vocab, n_requests=8, doc_len=96, question_len=16,
            max_new_tokens=16, gap=2),
    }


def bench_scenarios(arch_name="qwen3-1.7b", policies=POLICIES):
    """All scenarios x all policies.  One engine per policy (the jitted
    decode/plan programs are shared across its six scenario runs)."""
    arch, params = _setup(arch_name)
    traces = _traces(arch.vocab)
    rows = []
    for policy in policies:
        eng = ServingEngine(params, arch, _matrix_config(policy))
        for name, trace in traces.items():
            eng.run(trace, "warmup")    # compile this cell's shapes
                                        # (prefill buckets differ by
                                        # scenario) outside the timed run
            rep = eng.run(trace, name)
            rows.append(rep.summary_row())
    return rows


def bench_continuous_vs_sequential(arch_name="qwen3-1.7b", policy="BBC"):
    """Acceptance cell: >= 2x sequential greedy_generate on steady Zipfian,
    token-identical outputs."""
    arch, params = _setup(arch_name)
    cfg = _config(policy)
    trace = _traces(arch.vocab)["steady_zipfian"]
    eng = ServingEngine(params, arch, cfg)
    eng.run(trace, "warmup")
    rep = eng.run(trace, "steady_zipfian")
    sequential_baseline(params, arch, trace, cfg)       # warm the jits
    base = sequential_baseline(params, arch, trace, cfg,
                               "steady_zipfian")
    mismatches = sum(rep.outputs[r] != base.outputs[r] for r in rep.outputs)
    speedup = rep.tokens_per_s_wall / base.tokens_per_s_wall
    assert mismatches == 0, \
        f"{mismatches} sequences diverge from greedy_generate"
    assert speedup >= 2.0, \
        f"continuous batching only {speedup:.2f}x sequential"
    # Same-timebase TTFT (ISSUE 4 satellite re-pin): the baseline's TTFT is
    # its modeled prefill cost; the engine adds queueing on top, so its p50
    # may exceed the baseline's on oversubscribed traces — the column pair
    # is now meaningful, not a 0-vs-prefill artifact.
    assert base.p50_ttft > 0, "sequential TTFT must include prefill cost"
    return [
        ("continuous_vs_sequential", "engine_tok_s",
         round(rep.tokens_per_s_wall, 1)),
        ("continuous_vs_sequential", "sequential_tok_s",
         round(base.tokens_per_s_wall, 1)),
        ("continuous_vs_sequential", "speedup", round(speedup, 2)),
        ("continuous_vs_sequential", "outputs_identical", mismatches == 0),
        ("continuous_vs_sequential", "p50_ttft_engine",
         round(rep.p50_ttft, 1)),
        ("continuous_vs_sequential", "p50_ttft_sequential",
         round(base.p50_ttft, 1)),
    ]


def bench_fused_kernel(arch_name="qwen3-1.7b", policy="BBC"):
    """ISSUE 4 acceptance cell: the fused page-table-walking read path vs
    the dense (materializing) oracle on steady Zipfian — emitted tokens
    bit-identical; far rows touched == sum of live, non-promoted page rows
    (device walk accounting == independent host shadow), a fraction of the
    materializing path's ``n_pages * page * B``."""
    arch, params = _setup(arch_name)
    trace = _traces(arch.vocab)["steady_zipfian"]
    dense_eng = ServingEngine(params, arch, _config(policy))
    fused_eng = ServingEngine(params, arch, _config(policy, fused=True))
    dense_eng.run(trace, "warmup")
    dense = dense_eng.run(trace, "steady_zipfian")
    fused_eng.run(trace, "warmup")
    fused = fused_eng.run(trace, "steady_zipfian")
    assert dense.outputs == fused.outputs, \
        "fused kernel changed emitted tokens"
    assert fused.far_rows_touched == fused.far_rows_host, \
        "fused walk accounting diverges from the host shadow"
    assert fused.far_rows_touched < fused.far_rows_dense
    return [
        ("fused_kernel", "outputs_identical", dense.outputs == fused.outputs),
        ("fused_kernel", "far_rows_touched", fused.far_rows_touched),
        ("fused_kernel", "far_rows_host_shadow", fused.far_rows_host),
        ("fused_kernel", "far_rows_dense_equiv", fused.far_rows_dense),
        ("fused_kernel", "far_rows_saved_frac",
         round(fused.far_rows_saved_frac, 3)),
        ("fused_kernel", "fused_tok_s", round(fused.tokens_per_s_wall, 1)),
        ("fused_kernel", "dense_tok_s", round(dense.tokens_per_s_wall, 1)),
    ]


def bench_prefix_sharing(arch_name="qwen3-1.7b", policy="BBC"):
    """Acceptance cell: shared-system-prompt trace through the sharing and
    non-sharing engines — >= 40% fewer prefilled tokens, better modeled p50
    TTFT, bit-identical emitted tokens.  A multi-turn-chat cell reports the
    re-arrival hit rate alongside."""
    arch, params = _setup(arch_name)
    trace = SCENARIOS["shared_system_prompt"](
        arch.vocab, n_requests=10, sys_len=64, user_len=16,
        max_new_tokens=12, gap=2)
    base_eng = ServingEngine(params, arch, _config(policy))
    share_eng = ServingEngine(params, arch, _config(policy, share=True))
    base_eng.run(trace, "warmup")
    base = base_eng.run(trace, "shared_system_prompt")
    share_eng.run(trace, "warmup")
    share = share_eng.run(trace, "shared_system_prompt")
    assert base.outputs == share.outputs, \
        "prefix sharing changed emitted tokens"
    saved = share.prefill_saved_frac
    assert saved >= 0.4, f"only {saved:.0%} prefill tokens saved"
    assert share.p50_ttft < base.p50_ttft, \
        f"p50 TTFT regressed: {share.p50_ttft} vs {base.p50_ttft}"

    chat = SCENARIOS["multi_turn_chat"](arch.vocab, n_sessions=3, turns=3,
                                        base_len=32, turn_len=16,
                                        max_new_tokens=8, think_gap=24)
    chat_eng = ServingEngine(params, arch, _config(policy, share=True))
    chat_eng.run(chat, "warmup")
    chat_rep = chat_eng.run(chat, "multi_turn_chat")
    return [
        ("prefix_sharing", "prefill_tokens_saved_frac", round(saved, 3)),
        ("prefix_sharing", "prefix_hit_rate",
         round(share.prefix_hit_rate, 3)),
        ("prefix_sharing", "p50_ttft_base", round(base.p50_ttft, 1)),
        ("prefix_sharing", "p50_ttft_sharing", round(share.p50_ttft, 1)),
        ("prefix_sharing", "outputs_identical", base.outputs == share.outputs),
        ("prefix_sharing", "chat_prefix_hit_rate",
         round(chat_rep.prefix_hit_rate, 3)),
    ]


def bench_pool_native(arch_name="qwen3-1.7b", policy="BBC"):
    """ISSUE 5 acceptance cell: with the pool as the single source of truth
    (no dense per-slot KV master anywhere in the engine), peak live KV
    bytes — referenced pool pages + near-tier copies, all layers, K and V —
    must be <= 0.6x the dense-equivalent master's fixed footprint on the
    two sharing-heavy traces.  Zero orphaned pages is asserted by the
    engine's shutdown refcount sweep inside every run."""
    arch, params = _setup(arch_name)
    # shared-system-prompt: many tenants of one prompt prefix
    ssp = SCENARIOS["shared_system_prompt"](
        arch.vocab, n_requests=12, sys_len=64, user_len=16,
        max_new_tokens=16, gap=2)
    eng = ServingEngine(params, arch, _config(policy, share=True))
    eng.run(ssp, "warmup")
    rep = eng.run(ssp, "shared_system_prompt")
    # long-context summarize: few slots, one very long shared document
    lcs = SCENARIOS["long_context_summarize"](
        arch.vocab, n_requests=6, doc_len=192, question_len=16,
        max_new_tokens=8, gap=4)
    tier = TieredKVConfig(page=16, near_pages=2, interval=4, policy=policy)
    lcs_cfg = ServingConfig(n_slots=4, max_len=256, prefill_bucket=16,
                            tier=tier, share_prefix=True)
    lcs_eng = ServingEngine(params, arch, lcs_cfg)
    lcs_eng.run(lcs, "warmup")
    lcs_rep = lcs_eng.run(lcs, "long_context_summarize")
    for r in (rep, lcs_rep):
        assert r.kv_live_ratio <= 0.6, \
            f"{r.scenario}: live KV {r.kv_live_ratio:.3f}x dense (> 0.6)"
    return [
        ("pool_native", "ssp_kv_bytes_live", rep.kv_bytes_live),
        ("pool_native", "ssp_kv_bytes_dense_equiv",
         rep.kv_bytes_dense_equiv),
        ("pool_native", "ssp_kv_live_ratio", round(rep.kv_live_ratio, 3)),
        ("pool_native", "lcs_kv_bytes_live", lcs_rep.kv_bytes_live),
        ("pool_native", "lcs_kv_bytes_dense_equiv",
         lcs_rep.kv_bytes_dense_equiv),
        ("pool_native", "lcs_kv_live_ratio",
         round(lcs_rep.kv_live_ratio, 3)),
        ("pool_native", "lcs_prefill_saved_frac",
         round(lcs_rep.prefill_saved_frac, 3)),
    ]


def bench_chunked_prefill(arch_name="qwen3-1.7b", policy="BBC",
                          chunk=MATRIX_CHUNK):
    """ISSUE 8 acceptance cell: chunked admission prefill + overlapped
    migration vs the synchronous engine on the two stall-dominated traces.
    The overlap must not change a single emitted token (the chunk-resume
    step is bit-identical to one-shot prefill and the scheduler change is
    pure timing), while modeled p99 token latency and p50 TTFT both drop
    >= 25%: admission prefills no longer land whole inside in-flight
    requests' inter-token gaps, and queued requests stop serializing
    behind full-prompt prefills."""
    arch, params = _setup(arch_name)
    traces = _traces(arch.vocab)
    out = []
    for name in ("bursty", "long_context_stragglers"):
        trace = traces[name]
        sync_eng = ServingEngine(params, arch, _config(policy))
        over_eng = ServingEngine(params, arch,
                                 _config(policy, chunk=chunk, overlap=True))
        sync_eng.run(trace, "warmup")
        sync = sync_eng.run(trace, name)
        over_eng.run(trace, "warmup")
        over = over_eng.run(trace, name)
        assert sync.outputs == over.outputs, \
            f"{name}: chunked prefill changed emitted tokens"
        p99_gain = 1.0 - over.p99_lat / sync.p99_lat
        ttft_gain = 1.0 - over.p50_ttft / sync.p50_ttft
        assert p99_gain >= 0.25, \
            f"{name}: p99 latency only improved {p99_gain:.0%} " \
            f"({sync.p99_lat:.0f} -> {over.p99_lat:.0f})"
        assert ttft_gain >= 0.25, \
            f"{name}: p50 TTFT only improved {ttft_gain:.0%} " \
            f"({sync.p50_ttft:.0f} -> {over.p50_ttft:.0f})"
        out += [
            ("chunked_prefill", f"{name}_outputs_identical", True),
            ("chunked_prefill", f"{name}_p99_lat_sync",
             round(sync.p99_lat, 1)),
            ("chunked_prefill", f"{name}_p99_lat_chunked",
             round(over.p99_lat, 1)),
            ("chunked_prefill", f"{name}_p99_gain", round(p99_gain, 3)),
            ("chunked_prefill", f"{name}_p50_ttft_sync",
             round(sync.p50_ttft, 1)),
            ("chunked_prefill", f"{name}_p50_ttft_chunked",
             round(over.p50_ttft, 1)),
            ("chunked_prefill", f"{name}_ttft_gain", round(ttft_gain, 3)),
            ("chunked_prefill", f"{name}_prefill_chunks",
             over.prefill_chunks),
            ("chunked_prefill", f"{name}_migration_deferrals",
             over.migration_deferrals),
        ]
    return out


def bench_mesh_scaling(arch_name="qwen3-1.7b", policy="BBC", lanes=4):
    """ISSUE 10 acceptance cell: data-parallel serving over the mesh's
    'data' axis — R engine replicas, round-robin admissions by arrival,
    per-lane byte-cost clocks, fleet clock = slowest lane.  On the
    slot-bound steady Zipfian (4 slots, 48 uniform requests: each lane
    keeps its slots saturated long enough to amortize its admission
    ramp, and prefills stop serializing on a single clock) the modeled
    fleet throughput at 4 lanes must be >= 3x the single-lane engine, with emitted tokens
    bit-identical — decode tokens are batching-invariant, so
    partitioning the trace changes no token.  Lanes are host-modeled
    (every replica is the same jitted program with its own clock), so
    this cell runs on any device count; the kernel-level KV-head
    sharding is pinned by tests/test_mesh_serving.py on the mesh-4dev
    CI leg.  ``check_bench_regression`` gates every ``tok_per_kcost*``
    key in this cell, including the per-device column."""
    arch, params = _setup(arch_name)
    trace = SCENARIOS["steady_zipfian"](
        arch.vocab, n_requests=48, prompt_len=24, max_new_tokens=16, gap=1)
    cfg = _config(policy, n_slots=4)
    dp = DataParallelEngine(params, arch, cfg, n_replicas=lanes)
    dp.engine.run(trace, "warmup")          # one engine serves every lane:
                                            # compile once, reuse R+1 times
    single = dp.engine.run(trace, "steady_zipfian")
    fleet = dp.run(trace, "steady_zipfian")
    assert fleet.outputs == single.outputs, \
        "data-parallel lanes changed emitted tokens"
    assert fleet.tokens == single.tokens
    speedup = fleet.tokens_per_cost / single.tokens_per_cost
    assert speedup >= 3.0, \
        f"{lanes}-lane modeled throughput only {speedup:.2f}x single-lane"
    return [
        ("mesh_scaling", "lanes", lanes),
        ("mesh_scaling", "outputs_identical", True),
        ("mesh_scaling", "tok_per_kcost_1lane",
         round(single.tokens_per_cost * 1e3, 3)),
        ("mesh_scaling", "tok_per_kcost_fleet",
         round(fleet.tokens_per_cost * 1e3, 3)),
        ("mesh_scaling", "tok_per_kcost_per_device",
         round(fleet.tokens_per_cost / lanes * 1e3, 3)),
        ("mesh_scaling", "speedup_modeled", round(speedup, 2)),
    ]


def run_all(out_path: str | None = "BENCH_serving.json"):
    rows = [ServingReport.HEADER] + bench_scenarios()
    rows += bench_continuous_vs_sequential()
    rows += bench_prefix_sharing()
    rows += bench_fused_kernel()
    rows += bench_pool_native()
    rows += bench_chunked_prefill()
    rows += bench_mesh_scaling()
    for r in rows:
        print(",".join(str(x) for x in r))
    if out_path:
        header = ServingReport.HEADER
        matrix = [dict(zip(header, r)) for r in rows
                  if len(r) == len(header) and r != header]
        cells: dict = {}
        for r in rows:
            if len(r) == 3:
                cells.setdefault(r[0], {})[r[1]] = r[2]
        with open(out_path, "w") as f:
            json.dump({"matrix": matrix, "cells": cells}, f, indent=1)
        print(f"wrote {out_path}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run_all()
