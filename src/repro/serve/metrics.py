"""Modeled byte-cost accounting + report types for the serving engine.

The cost model extends the tier cost landscape (`repro.tier.costs`, units:
relative byte-costs — only ratios matter) from the per-access level to the
per-decode-step level:

  step cost     = ``step_overhead``                (weight streaming: decode
                                                    is weight-bandwidth-bound
                                                    at small batch — the term
                                                    continuous batching
                                                    amortizes)
                + per-slot KV read cost            (near tokens at
                                                    ``near_cost``, the rest of
                                                    the live prefix
                                                    gather-addressed at
                                                    ``far_cost``)
  prefill cost  = ``prefill_token_cost`` x prompt tokens + ``step_overhead``
  migration     = pages moved x page x ``migrate_cost`` (the IST bill)

Latency-per-token is the modeled-clock gap between a token and the previous
token of the same sequence (first token: gap since the request's arrival —
queueing delay included), which is how serving systems report inter-token
latency and TTFT.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.tier import TierCosts
from repro.core.tiered_kv import DEFAULT_COSTS


@dataclass
class CostModel:
    step_overhead: float = 2048.0   # weight-stream cost per decode step
    prefill_token_cost: float = 2.0
    tier: TierCosts = DEFAULT_COSTS

    def decode_step_cost(self, near_tokens: np.ndarray,
                         live_tokens: np.ndarray,
                         kv_shards: int = 1) -> float:
        """near_tokens/live_tokens: per-active-slot arrays (near <= live).

        ``kv_shards``: number of devices the KV pool is head-sharded
        across (docs/design.md §2h).  Each device streams only its
        1/kv_shards slice of the KV bytes, so the KV term divides; the
        ``step_overhead`` weight stream does NOT — weights are replicated
        on every device of the mesh."""
        far = np.maximum(live_tokens - near_tokens, 0)
        kv = (near_tokens * self.tier.near_cost + far * self.tier.far_cost)
        return float(self.step_overhead + kv.sum() / max(int(kv_shards), 1))

    def prefill_cost(self, prompt_tokens: int) -> float:
        return self.step_overhead + self.prefill_token_cost * prompt_tokens

    def chunk_prefill_cost(self, chunk_tokens: int) -> float:
        """Prefill tokens piggybacked on a decode tick (Sarathi-style
        chunked prefill): the chunk's tokens are billed to the tick, the
        step overhead is NOT — the chunk shares the tick's weight stream.
        A prefill-only tick (no active decoders) still pays
        ``prefill_cost`` (it streams the weights for nobody else)."""
        return self.prefill_token_cost * chunk_tokens

    def migration_cost(self, pages_moved: int, page: int) -> float:
        return float(pages_moved) * page * self.tier.migrate_cost


def percentiles(xs, qs=(50, 99)) -> tuple[float, ...]:
    if not len(xs):
        return tuple(float("nan") for _ in qs)
    return tuple(float(np.percentile(np.asarray(xs, np.float64), q))
                 for q in qs)


@dataclass
class ServingReport:
    scenario: str
    policy: str
    n_requests: int
    tokens: int = 0
    steps: int = 0                   # batched decode steps executed
    wall_s: float = 0.0
    modeled_time: float = 0.0        # byte-cost clock at completion
    token_latencies: list = field(default_factory=list)   # modeled units
    ttfts: list = field(default_factory=list)   # first-token latencies
                                                # (queueing + prefill)
    near_hit_mass: list = field(default_factory=list)     # per planning pass
    migrations: int = 0
    outputs: dict = field(default_factory=dict)           # rid -> [tokens]
    slot_history: dict = field(default_factory=dict)      # slot -> [rids]
    max_read_err: float = 0.0        # tiered read-path verification residual
    # prefix-sharing accounting (zero when sharing is off)
    prefill_tokens: int = 0          # tokens actually prefilled (suffixes)
    prefill_tokens_full: int = 0     # tokens a no-sharing engine prefills
    prefix_hit_tokens: int = 0       # prompt tokens served from cached pages
    prefix_lookups: int = 0
    prefix_hits: int = 0
    # far-tier rows read per decode step, accumulated over the run (per
    # sequence per step, layer-invariant).  The fused walk touches only
    # live, non-promoted pages; the materializing path touches the whole
    # (B, n_pages*page) far view regardless (ISSUE 4 acceptance).
    far_rows_touched: int = 0        # what the configured read path touched
    far_rows_host: int = 0           # independent host-side shadow of the
                                     # fused walk (device/host parity pin)
    far_rows_dense: int = 0          # what a materializing path would touch
    # live-KV accounting (ISSUE 5): with the pool as the single source of
    # truth, what the engine actually keeps resident vs what the retired
    # dense per-slot master would have held.  Near-tier rows are *derived
    # copies* of pool bytes (TL-DRAM's near segment is the same mat behind
    # an isolation transistor, not extra capacity) — they are accounted in
    # their own column, never against the dense-equiv denominator, which
    # never included a near tier either (the kv_live_ratio > 1.0 bench lie,
    # ISSUE 8 satellite).
    kv_bytes_live: int = 0           # PEAK referenced-pool bytes over the
                                     # run (all layers, K and V)
    kv_bytes_near: int = 0           # peak occupied near-tier copy bytes
    kv_bytes_cached: int = 0         # peak prefix-retained idle bytes
                                     # (reclaimable cache, not live state)
    kv_bytes_dense_equiv: int = 0    # L * n_slots * max_len rows x2 — the
                                     # dense master's fixed footprint
    # overlap accounting (ISSUE 8 tentpole)
    prefill_chunks: int = 0          # chunked-prefill programs launched
    migration_deferrals: int = 0     # planning passes skipped by the
                                     # cost-aware deferral gate
    migration_stall: float = 0.0     # modeled time the background
                                     # migration lane was saturated
    # what the tick loop did on the host, on the wall clock
    # (``time.perf_counter`` seconds); recorded where the work happens,
    # with no device read of their own
    host_syncs: int = 0              # blocking reads of device values
                                     # (``ServingEngine._host``)
    decode_ticks: list = field(default_factory=list)
                                     # per decode step: (t_dispatch,
                                     # t_tokens, active slots, live rows =
                                     # sum of pos + 1 over them)
    prefill_rows: list = field(default_factory=list)
                                     # per prefill chunk: (first row,
                                     # end row) of the prompt it wrote
    request_wall: dict = field(default_factory=dict)
                                     # rid -> [visible, admitted, first
                                     # token, last token] (nan: not yet)

    @property
    def tokens_per_s_wall(self) -> float:
        return self.tokens / max(self.wall_s, 1e-9)

    @property
    def tokens_per_cost(self) -> float:
        """Modeled throughput: tokens per unit of byte-cost."""
        return self.tokens / max(self.modeled_time, 1e-9)

    @property
    def mean_hit_mass(self) -> float:
        return float(np.mean(self.near_hit_mass)) if self.near_hit_mass \
            else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prompt tokens whose KV came from the prefix cache."""
        return self.prefix_hit_tokens / max(self.prefill_tokens_full, 1)

    @property
    def prefill_saved_frac(self) -> float:
        """Fraction of prefill tokens the sharing path avoided computing."""
        return 1.0 - self.prefill_tokens / max(self.prefill_tokens_full, 1)

    @property
    def p50_ttft(self) -> float:
        return percentiles(self.ttfts, qs=(50,))[0]

    @property
    def p99_lat(self) -> float:
        return percentiles(self.token_latencies, qs=(99,))[0]

    @property
    def kv_live_ratio(self) -> float:
        """Peak live KV bytes as a fraction of the dense-equivalent master
        (<= 1.0 ALWAYS — each slot maps at most its max_len of pages and
        shared pages count once; the engine asserts this per tick.  The
        shared/long-prefix traces pin <= 0.6)."""
        if self.kv_bytes_dense_equiv == 0:
            return 0.0
        return self.kv_bytes_live / self.kv_bytes_dense_equiv

    @property
    def far_rows_saved_frac(self) -> float:
        """Fraction of far-view rows the configured read path did NOT touch
        vs the materializing baseline (0.0 for the dense path itself, and
        0.0 for runs that tracked no far-row accounting at all, e.g. the
        sequential baseline)."""
        if self.far_rows_dense == 0:
            return 0.0
        return 1.0 - self.far_rows_touched / self.far_rows_dense

    def summary_row(self) -> tuple:
        p50, p99 = percentiles(self.token_latencies)
        return (self.scenario, self.policy, self.tokens,
                round(self.tokens_per_s_wall, 1),
                round(self.tokens_per_cost * 1e3, 3),
                round(self.mean_hit_mass, 3), self.migrations,
                round(p50, 1), round(p99, 1),
                round(self.prefix_hit_rate, 3), self.prefill_tokens,
                round(self.p50_ttft, 1), self.far_rows_touched,
                self.kv_bytes_live, round(self.kv_live_ratio, 3))

    HEADER = ("scenario", "policy", "tokens", "tok/s_wall",
              "tok/kcost_modeled", "near_hit_mass", "migrations",
              "p50_lat", "p99_lat", "prefix_hit_rate", "prefill_toks",
              "p50_ttft", "far_rows", "kv_bytes_live", "kv_live_ratio")


def merge_lane_reports(lanes: list) -> "ServingReport":
    """Fold per-replica lane reports into one fleet-level ServingReport.

    Data-parallel serving (docs/design.md §2h) runs R independent engine
    replicas, each with its own slot pool and modeled byte-cost clock.
    Counters sum; latency/TTFT samples concatenate (each sample is already
    on its own lane's clock); peak-byte columns sum (each lane owns
    distinct HBM); ``modeled_time`` is the MAX lane clock — the fleet is
    done when its slowest lane is — so ``tokens_per_cost`` reflects the
    per-device weight stream running R-wide in parallel.
    """
    if not lanes:
        raise ValueError("merge_lane_reports: no lanes")
    head = lanes[0]
    merged = ServingReport(
        scenario=head.scenario, policy=head.policy,
        n_requests=sum(r.n_requests for r in lanes))
    for f in ("tokens", "steps", "migrations", "prefill_tokens",
              "prefill_tokens_full", "prefix_hit_tokens", "prefix_lookups",
              "prefix_hits", "far_rows_touched", "far_rows_host",
              "far_rows_dense", "kv_bytes_live", "kv_bytes_near",
              "kv_bytes_cached", "kv_bytes_dense_equiv", "prefill_chunks",
              "migration_deferrals", "host_syncs"):
        setattr(merged, f, sum(getattr(r, f) for r in lanes))
    merged.wall_s = max(r.wall_s for r in lanes)
    merged.modeled_time = max(r.modeled_time for r in lanes)
    merged.migration_stall = sum(r.migration_stall for r in lanes)
    merged.max_read_err = max(r.max_read_err for r in lanes)
    for r in lanes:
        merged.token_latencies.extend(r.token_latencies)
        merged.ttfts.extend(r.ttfts)
        merged.near_hit_mass.extend(r.near_hit_mass)
        merged.decode_ticks.extend(r.decode_ticks)
        merged.prefill_rows.extend(r.prefill_rows)
        merged.request_wall.update(r.request_wall)
        merged.outputs.update(r.outputs)
    for i, r in enumerate(lanes):
        for slot, rids in r.slot_history.items():
            merged.slot_history[(i, slot)] = rids
    return merged
