"""The serving engine's own spans and tick-loop counters.

The engine writes ``serve.*`` spans on the profiler's host plane and counts
on the host what each tick did (``ServingReport.host_syncs``,
``decode_ticks``, ``prefill_rows``, ``request_wall``).  Pinned here, on a
small CPU engine with chunked prefill, prefix sharing and the fused walk:
the spans nest as documented and agree with the counters; the counters
agree with what a recorder wrapped around the engine's decode program and
prefill lane sees from outside (the benchmark's old view); the benchmark's
tick-loop metrics read exact values off a hand-built trace; and the
counters survive merging data-parallel lane reports.
"""

import importlib.util
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core.tiered_kv import TieredKVConfig
from repro.models import transformer
from repro.serve import ServingConfig, ServingEngine
from repro.serve.metrics import ServingReport, merge_lane_reports
from repro.serve.trace import Request

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402
import tick_spans  # noqa: E402

WAITS = {"serve.wait.tokens", "serve.wait.walk_rows", "serve.wait.mapping",
         "serve.wait.masses", "serve.wait.migrations",
         "serve.wait.first_token"}


def _trace(vocab, rng):
    """Two 40-token prompts sharing 32 tokens, then private ones: chunked
    prefills resume mid-prompt and after a prefix hit."""
    shared = rng.integers(0, vocab, 32).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, vocab, 8)]),
               np.concatenate([shared, rng.integers(0, vocab, 8)]),
               rng.integers(0, vocab, 20), rng.integers(0, vocab, 36),
               rng.integers(0, vocab, 12)]
    return [Request(rid=i, arrival=2 * i, prompt=p.astype(np.int32),
                    max_new_tokens=10 - i)
            for i, p in enumerate(prompts)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced run, with the recorder wrapped around the engine."""
    arch = ARCHS["qwen3-1.7b"].reduced()
    params = transformer.init_params(jax.random.key(0), arch)
    tier = TieredKVConfig(page=16, near_pages=2, interval=3, policy="SC",
                          fused_kernel=True)
    cfg = ServingConfig(n_slots=3, max_len=64, prefill_bucket=16, tier=tier,
                        share_prefix=True, prefill_chunk_tokens=16,
                        overlap_migration=True)
    eng = ServingEngine(params, arch, cfg)
    trace = _trace(arch.vocab, np.random.default_rng(3))
    eng.run(trace)                                   # compile outside

    seen = SimpleNamespace(calls=[], spans=[])
    decode, advance = eng._decode, eng._advance_prefills

    def recorded_decode(*args):
        idx = [b for b, s in enumerate(eng.slots) if s is not None]
        seen.calls.append((len(idx), int((eng.pos[idx] + 1).sum())))
        return decode(*args)

    def recorded_advance(budget):
        before = {s: (j.cursor, j.S) for s, j in eng.pending.items()}
        out = advance(budget)
        for s, (c0, S) in before.items():
            job = eng.pending.get(s)
            c1 = S if job is None else job.cursor
            if c1 > c0:
                seen.spans.append((c0, c1))
        return out

    eng._decode, eng._advance_prefills = recorded_decode, recorded_advance
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        report = eng.run(trace)
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        str(tr.newest_xplane(out)))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for p in data.planes if p.name == "/host:CPU"
              for line in p.lines for e in line.events
              if e.name.startswith("serve.")]
    return SimpleNamespace(report=report, events=events, seen=seen)


def _named(events, pred):
    return [(s, e) for n, s, e in events if pred(n)]


def _inside(inner, outer):
    return all(any(a <= s and e <= b for a, b in outer) for s, e in inner)


def test_spans_nest(served):
    ev = served.events
    runs = _named(ev, "serve.run".__eq__)
    ticks = _named(ev, "serve.tick".__eq__)
    assert len(runs) == 1 and ticks
    assert _inside(ticks, runs)
    assert _inside(_named(ev, lambda n: n.startswith("serve.wait.")), ticks)
    for phase in ("serve.admit", "serve.prefill", "serve.decode",
                  "serve.maintain"):
        assert _named(ev, phase.__eq__), phase
        assert _inside(_named(ev, phase.__eq__), ticks), phase
    for phase in ("serve.setup", "serve.teardown"):
        assert len(_named(ev, phase.__eq__)) == 1
        assert _inside(_named(ev, phase.__eq__), runs)


def test_every_blocking_read_is_one_wait_span(served):
    waits = [n for n, _, _ in served.events if n.startswith("serve.wait.")]
    assert len(waits) == served.report.host_syncs
    assert set(waits) == WAITS


def test_decode_spans_count_steps_and_decode_ticks(served):
    decodes = _named(served.events, "serve.decode".__eq__)
    rep = served.report
    assert len(decodes) == rep.steps == len(rep.decode_ticks)


def test_counters_match_the_outside_recorder(served):
    rep = served.report
    assert [(a, l) for _, _, a, l in rep.decode_ticks] == served.seen.calls
    assert rep.prefill_rows == served.seen.spans
    assert rep.prefill_chunks == len(rep.prefill_rows)
    t = np.asarray([(d, k) for d, k, _, _ in rep.decode_ticks])
    assert (t[:, 0] < t[:, 1]).all() and (np.diff(t[:, 0]) > 0).all()


def test_request_wall_times_are_in_order(served):
    rep = served.report
    assert sorted(rep.request_wall) == sorted(rep.outputs)
    for wall in rep.request_wall.values():
        assert np.isfinite(wall).all()
        assert wall == sorted(wall)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"tracing_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _hand_trace():
    """Window 0-100 ms.  Two ticks, each with one decode and one wait; the
    chip idles in both, between them, in set-up and teardown, and in a gap
    that no span covers (8-10, 40-50, 80-85 ms)."""
    ms = 1e6
    ev = lambda name, s, e: tr.Event(name, s * ms, e * ms)
    ops = [ev("op", s, e) for s, e in
           ((0, 5), (12, 28), (32, 34), (52, 72), (90, 95))]
    host = [ev("serve.setup", 0, 8), ev("serve.teardown", 85, 100),
            ev("serve.tick", 10, 40), ev("serve.tick", 50, 80),
            ev("serve.decode", 20, 35), ev("serve.decode", 55, 75),
            ev("serve.wait.tokens", 30, 35),
            ev("serve.wait.tokens", 70, 75)]
    return tr.Trace(devices={"/device:TPU:0": tr.Device(ops=ops)},
                    host=host, window=(0.0, 100 * ms))


@pytest.mark.parametrize("name,want", [
    ("tick_host_ms", (60 - 10) / 2),       # tick time less waits, per tick
    ("idle_host_ms_per_tick", 16 / 2),     # 2 + 4 + 6 + 2 + 8 less waits' 6
    ("idle_wait_ms_per_tick", 6 / 2),      # 30-32, 34-35, 72-75
])
def test_tick_metrics_on_a_hand_built_trace(name, want):
    assert _reader(name)(SimpleNamespace(trace=_hand_trace())) == \
        pytest.approx(want, abs=1e-9)


def test_idle_account_closes_on_a_hand_built_trace():
    acc = tick_spans.account(_hand_trace())
    assert acc.idle_s == pytest.approx(52e-3)
    assert (acc.idle_setup_s, acc.idle_teardown_s, acc.idle_other_s) == \
        pytest.approx((3e-3, 10e-3, 17e-3))
    assert acc.idle_host_s + acc.idle_wait_s + acc.idle_setup_s \
        + acc.idle_teardown_s + acc.idle_other_s == pytest.approx(acc.idle_s)
    assert acc.waits == {"serve.wait.tokens": 2}


@pytest.mark.parametrize("name", ["tick_host_ms", "idle_host_ms_per_tick",
                                  "idle_wait_ms_per_tick"])
def test_tick_metrics_read_nothing_without_engine_spans(name):
    t = _hand_trace()
    t.host = [h for h in t.host if not h.name.startswith("serve.")]
    assert _reader(name)(SimpleNamespace(trace=t)) is None
    assert _reader(name)(SimpleNamespace(trace=None)) is None


def test_host_syncs_per_tick():
    read = _reader("host_syncs_per_tick")
    reps = [ServingReport("a", "SC", 1, steps=4, host_syncs=10),
            ServingReport("b", "SC", 1, steps=6, host_syncs=15)]
    assert read(SimpleNamespace(reports=reps)) == 2.5
    # an engine that does not count them reads nothing
    old = SimpleNamespace(steps=4)
    assert read(SimpleNamespace(reports=[old])) is None


def test_merge_lane_reports_carries_the_tick_counters():
    now = time.perf_counter()
    a = ServingReport("t", "SC", 1, host_syncs=3,
                      decode_ticks=[(now, now + 1, 2, 40)],
                      prefill_rows=[(0, 16)],
                      request_wall={0: [now, now, now + 1, now + 2]})
    b = ServingReport("t", "SC", 1, host_syncs=4,
                      decode_ticks=[(now, now + 2, 1, 20)],
                      prefill_rows=[(16, 30)],
                      request_wall={1: [now, now + 1, now + 2, now + 3]})
    m = merge_lane_reports([a, b])
    assert m.host_syncs == 7
    assert m.decode_ticks == a.decode_ticks + b.decode_ticks
    assert m.prefill_rows == [(0, 16), (16, 30)]
    assert m.request_wall == {**a.request_wall, **b.request_wall}
