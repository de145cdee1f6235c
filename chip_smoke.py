#!/usr/bin/env python3
"""Serve a few requests through the tiered-KV engine on a TPU at
Qwen3-1.7B's published widths, and check what comes out.

    python3 chip_smoke.py             # one chip: dense and fused read paths
    python3 chip_smoke.py --chips 4   # KV-head-sharded fused engine on a
                                      # 4-chip mesh vs one chip of it

The one-chip run builds all 28 layers (d_model 2048, 16 query / 8 KV heads,
head_dim 128, vocab 151,936) from seeded random bf16 weights and serves the
same trace twice through ``ServingEngine(params, arch, cfg).run(trace)``:
once through the dense XLA read path and once through the fused page-table
walk, both under BBC with prefix sharing, chunked prefill and overlapped
migration.  It fails unless every request gets its tokens, the engine's own
``verify_tiered_read`` probe (paged tiered read vs attention over the
materialized pool view) stays within bf16 rounding, the fused walk's far
rows touched equal the engine's host-side recount and stay below the dense
path's, and both read paths emit the same first token (one prefill program
serves both).  Token agreement past the first token is reported, not
asserted: bf16 batching on the chip need not be bit-identical.

Every line on stdout is one JSON object; the last is
``{"ok": true, "device": {...}}``.  With no TPU, or when any phase fails,
the script exits non-zero and prints no such line.  It starts no other
process: the chip belongs to this one.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
N_REQUESTS = 4
PROMPT_LEN = 1024         # two prefill chunks per prompt
NEW_TOKENS = 32
CHUNK = 512               # prefill_chunk_tokens: one page-aligned shape
N_SLOTS = 8
MAX_LEN = 4096            # default pool: (8 + 4) * 256 pages ~ 5.6 GB of KV
READ_ERR_LIMIT = 3e-2     # a few bf16 ulps of O(1) attention outputs
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def emit(**fields):
    print(json.dumps(fields), flush=True)


def tpu_devices(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < n:
        raise SystemExit(f"chip_smoke: needs {n} TPU devices, JAX found "
                         f"{len(devices)}")
    return devices


class CompileLog:
    """Counts XLA programs compiled (or loaded from the persistent cache)
    and the seconds spent on them, through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.programs, self.seconds, self.cache_hits = 0, 0.0, 0

        def on_duration(event, secs, **_):
            if event == COMPILE_EVENT:
                self.programs += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.programs, self.seconds, self.cache_hits

    def since(self, snap):
        return {"programs": self.programs - snap[0],
                "compile_s": self.seconds - snap[1],
                "cache_hits": self.cache_hits - snap[2]}


def make_trace(vocab: int):
    """Fixed-length prompts; requests 2 and 3 share their first chunk with
    requests 0 and 1 and arrive after it is indexed, so the prefix cache
    serves it and their prefill resumes at the same cursor as every other
    second chunk: the whole run compiles two prefill shapes."""
    from repro.serve.trace import Request
    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, vocab, (N_REQUESTS, PROMPT_LEN), np.int32)
    prompts[2, :CHUNK] = prompts[0, :CHUNK]
    prompts[3, :CHUNK] = prompts[1, :CHUNK]
    arrivals = (0, 0, 2, 3)
    return [Request(rid=i, arrival=arrivals[i], prompt=prompts[i],
                    max_new_tokens=NEW_TOKENS) for i in range(N_REQUESTS)]


def serving_config(fused: bool, mesh=None):
    from repro.core.tiered_kv import TieredKVConfig
    from repro.serve import ServingConfig
    tier = TieredKVConfig(page=16, near_pages=8, interval=8, policy="BBC",
                          fused_kernel=fused, mesh=mesh)
    return ServingConfig(n_slots=N_SLOTS, max_len=MAX_LEN, tier=tier,
                         share_prefix=True, prefill_chunk_tokens=CHUNK,
                         overlap_migration=True, verify_tiered_read=True)


def build_params(arch, sharding):
    import jax
    import jax.numpy as jnp
    from repro.models import transformer
    init = jax.jit(lambda key: transformer.init_params(key, arch,
                                                       dtype=jnp.bfloat16),
                   out_shardings=sharding)
    params = init(jax.random.key(SEED))
    jax.block_until_ready(params)
    return params


def serve(params, arch, cfg, trace, log: CompileLog, label: str):
    """One engine, one pass over the trace; the engine and its pools are
    dropped on return so the next engine has the chip's memory."""
    from repro.serve import ServingEngine
    snap = log.snapshot()
    t0 = time.perf_counter()
    report = ServingEngine(params, arch, cfg).run(trace, label)
    wall = time.perf_counter() - t0
    check_outputs(report, trace, arch.vocab, label)
    return report, {"read_path": label, "tokens": report.tokens,
                    "decode_steps": report.steps, "wall_s": wall,
                    **log.since(snap),
                    "max_read_err": report.max_read_err,
                    "far_rows_touched": report.far_rows_touched,
                    "migrations": report.migrations,
                    "prefix_hit_tokens": report.prefix_hit_tokens}


def check_outputs(report, trace, vocab: int, label: str):
    for req in trace:
        toks = report.outputs.get(req.rid)
        if toks is None or len(toks) != req.max_new_tokens:
            raise AssertionError(f"{label}: request {req.rid} got "
                                 f"{toks and len(toks)} of "
                                 f"{req.max_new_tokens} tokens")
        if not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"{label}: request {req.rid} emitted a "
                                 f"token outside the vocabulary")
    if report.tokens != sum(r.max_new_tokens for r in trace):
        raise AssertionError(f"{label}: served {report.tokens} tokens")


def agreement(a, b) -> float:
    return sum(a.outputs[r] == b.outputs[r] for r in a.outputs) \
        / len(a.outputs)


def first_divergence(a, b) -> list:
    """Per request, the index of the first token that differs between two
    runs (None where every token agrees)."""
    return [next((i for i, (x, y) in enumerate(zip(a.outputs[r],
                                                   b.outputs[r])) if x != y),
                 None) for r in sorted(a.outputs)]


def one_chip(log: CompileLog):
    import jax
    from repro.configs.registry import ARCHS
    arch = ARCHS["qwen3-1.7b"]
    device = jax.devices()[0]
    snap = log.snapshot()
    params = build_params(arch, jax.sharding.SingleDeviceSharding(device))
    emit(phase="params", arch=arch.name, layers=arch.n_layers,
         d_model=arch.d_model, vocab=arch.vocab, dtype="bfloat16",
         **log.since(snap))
    trace = make_trace(arch.vocab)
    reports = {}
    for label, fused in (("dense", False), ("fused", True)):
        reports[label], line = serve(params, arch, serving_config(fused),
                                     trace, log, label)
        emit(phase="serve", **line)
    dense, fused = reports["dense"], reports["fused"]
    for label, rep in reports.items():
        if not (math.isfinite(rep.max_read_err)
                and rep.max_read_err <= READ_ERR_LIMIT):
            raise AssertionError(f"{label}: verify_tiered_read max error "
                                 f"{rep.max_read_err} > {READ_ERR_LIMIT}")
    if fused.far_rows_touched != fused.far_rows_host:
        raise AssertionError(f"fused walk touched {fused.far_rows_touched} "
                             f"far rows, host recount {fused.far_rows_host}")
    if not fused.far_rows_touched < dense.far_rows_touched:
        raise AssertionError("fused walk touched no fewer far rows than the "
                             "dense view")
    first = [dense.outputs[r][0] == fused.outputs[r][0]
             for r in dense.outputs]
    if not all(first):
        raise AssertionError(f"first tokens differ between read paths: "
                             f"{first}")
    mem = device.memory_stats() or {}
    emit(phase="compare", token_agreement=agreement(dense, fused),
         first_divergence=first_divergence(dense, fused),
         far_rows_fused=fused.far_rows_touched,
         far_rows_dense=dense.far_rows_touched,
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         programs_total=log.programs, compile_s_total=log.seconds)


def four_chips(log: CompileLog):
    """The KV-head-sharded fused engine on a ('data' 1, 'model' 4) mesh vs
    the same trace on one device of that mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, \
        SingleDeviceSharding
    from repro.configs.registry import ARCHS
    from repro.launch.mesh import make_test_mesh
    arch = ARCHS["qwen3-1.7b"]
    mesh = make_test_mesh(4)
    trace = make_trace(arch.vocab)
    params = build_params(arch, NamedSharding(mesh, PartitionSpec()))
    sharded, _ = serve(params, arch, serving_config(True, mesh), trace,
                       log, "fused-sharded")
    del params
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    params = build_params(arch, SingleDeviceSharding(mesh.devices.flat[0]))
    single, _ = serve(params, arch, serving_config(True), trace, log,
                      "fused-one-device")
    emit(phase="four_chips", token_agreement=agreement(sharded, single),
         peak_bytes_in_use_after_sharded=peaks,
         peak_bytes_in_use_after_both=[
             (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the KV-head-sharded path and its "
                         "one-device comparison")
    args = ap.parse_args(argv)
    devices = tpu_devices(args.chips)

    from repro.launch.compile_cache import enable_compile_cache
    emit(phase="setup", compile_cache=enable_compile_cache())
    log = CompileLog()
    (four_chips if args.chips == 4 else one_chip)(log)

    d = devices[0]
    emit(ok=True, device={"platform": d.platform, "kind": d.device_kind,
                          "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
