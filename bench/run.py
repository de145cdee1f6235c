#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

    python3 bench/run.py --workload qwen3-1.7b.doc_qa --seed 7 \
        --seconds 10 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``).
The configuration names its model family (``families/<family>.py``),
from which the run takes the sizes, the weights, the program's
architecture, the reference and the accounting.  Set-up makes the
weights on the device from the seed, builds one ``ServingEngine`` and
warms it with one whole replay of the cell's trace.
The window is back-to-back replays of that trace on the same engine,
ending with the first replay that finishes at or after ``--seconds``.
``--trace 1`` runs the window under the profiler and reports the
per-layer metrics (``metrics/<name>.py``) instead of the end-to-end ones.
After the window the engine is freed and ``correctness.py`` scores a
sample of what it served against the float32 reference.

Two options exist for reading the check's other side on the chip, and the
benchmark's own runs use neither: ``--control <name>`` (one of the
family's ``CONTROLS``; dense: ``fp8``, ``fp8_kv``) scores the
lower-precision reference's picks in place of the served
tokens, and ``--fault <name>`` plants a fault of ``faults.py`` under the
timed path.  Either has to come out ``correct: false``.

The last line of stdout is one JSON object.  With no TPU, or fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".bench_out"
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
sys.path.insert(0, str(HERE))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="a lower-precision pass of the family's "
                         "reference (dense: fp8, fp8_kv)")
    ap.add_argument("--fault", default=None)
    return ap.parse_args(argv)


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, traced: bool) -> list:
    if not traced:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax():
    """Persistent compile cache at a fixed path inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} TPU chips, JAX found "
                         f"{len(devs)}")
    return devs


class CompileLog:
    """Programs compiled or loaded from the persistent cache (JAX's
    backend-compile events), and how many of them were cache hits."""

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


@dataclass
class Context:
    """What the metric readers see."""
    family: object                      # the configuration's family module
    dm: object
    peaks: dict
    reports: list
    window_s: float
    setup_s: float
    itl_s: object
    decode_calls: list                  # hooks.DecodeCall per tick
    prefill_spans: list                 # (first row, end row) per job/tick
    pool_bytes: int                     # the engine's K/V page pool
    passes: int
    compiles_in_window: int
    memory_peak_bytes: int
    trace: object = None
    busy_s: float = 0.0
    trace_window_s: float = 0.0
    layer_s: dict = field(default_factory=dict)


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True, config: dict | None = None,
             mix: dict | None = None, tamper=None, peaks: dict | None = None,
             limit: float | None = None, control: str | None = None) -> dict:
    """One run of one cell; returns the result object.  The keyword
    arguments exist for the CPU tests and the control: a smaller
    configuration and mix, a fault planted in the engine, peaks and a
    limit of their own, and the lower-precision reference scored in the
    program's place."""
    setup_jax()
    import jax

    import correctness
    import families
    import hooks
    import workload as traffic

    bench = benchmark()
    cell = cell_of(bench, workload)
    devs = chips(cell["chips"]) if require_tpu else jax.devices()
    dev = devs[0]
    peaks = peaks or peaks_for(dev.device_kind)
    cfg = config or families.load_config(cell["config"])
    fam = families.of(cfg)
    if control is not None and control not in fam.CONTROLS:
        raise SystemExit(f"bench: the {cfg['family']!r} family's reference "
                         f"has no control {control!r}; {fam.CONTROLS}")
    mix = mix or traffic.load_mix(cell["traffic"])
    dm = fam.dims(cfg)
    log = CompileLog()

    t = time.perf_counter()
    params = fam.program_params(seed, dm)
    t_weights = time.perf_counter() - t
    reqs = traffic.make_requests(mix, seed, dm.vocab)
    trace = hooks.requests(reqs)
    eng = hooks.ServingEngine(params, fam.arch_config(cfg, dm),
                              hooks.serving_config(cfg["engine"]))
    probe = hooks.EngineProbe(eng)
    if tamper is not None:
        tamper(eng)
    t = time.perf_counter()
    warm = probe.replay(trace)
    t_warm = time.perf_counter() - t
    warm_programs, warm_hits = log.programs, log.cache_hits

    probe.reset()
    tdir = OUT / "trace"
    if traced:
        shutil.rmtree(tdir, ignore_errors=True)
        # the Python tracer would log every engine call and slow the host
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    before = log.programs
    reports = []
    setup_s = time.perf_counter() - T0
    with jax.profiler.TraceAnnotation("bench.window"):
        tw = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.replay"):
                reports.append(probe.replay(trace))
            if time.perf_counter() - tw >= seconds:
                break
        window_s = time.perf_counter() - tw
    if traced:
        jax.profiler.stop_trace()
    in_window = log.programs - before
    mem = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell["chips"]]), default=0)

    interval = eng.cfg.tier.interval
    ctx = Context(
        family=fam, dm=dm, peaks=peaks, reports=reports, window_s=window_s,
        setup_s=setup_s, itl_s=probe.itl_samples(),
        decode_calls=probe.calls(), prefill_spans=list(probe.spans),
        pool_bytes=hooks.pool_bytes(eng, fam.pool_row_bytes(dm)),
        passes=sum(r.steps // interval - r.migration_deferrals
                   for r in reports),
        compiles_in_window=in_window, memory_peak_bytes=int(mem))
    breakdown = None
    if traced:
        import trace_reduce as tr
        ctx.trace = tr.load(tr.newest_xplane(tdir))
        ctx.busy_s = tr.busy_s(ctx.trace)
        ctx.trace_window_s = tr.window_s(ctx.trace)
        ctx.layer_s = tr.layer_seconds(ctx.trace)
        breakdown = {"device_ops": tr.top_ops(ctx.trace),
                     "idle_gaps": tr.idle_gaps(ctx.trace)}

    metrics = {}
    for m in metrics_of(bench, workload, traced):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- correctness, once the window's state is gone -------------------------
    outputs = reports[-1].outputs
    failed = sum(correctness.failed_requests(reqs, r.outputs, dm.vocab)
                 for r in reports)
    ticks = len(probe.t)
    hooks.free(eng)
    del probe, eng, params, warm
    gc.collect()
    picks = correctness.sample(reqs, seed)
    t = time.perf_counter()
    scored = correctness.served_gap(fam, seed, dm, reqs, outputs, picks,
                                    (control,) if control else ())
    gap = scored["controls"][control] if control else scored["gap"]
    t_check = time.perf_counter() - t
    if limit is None:
        limit = correctness.limits(workload)["served_logit_gap"]
    checks = {"served_logit_gap": {"value": gap, "limit": limit},
              "requests_failed": {"value": failed, "limit": 0}}
    correct = bool(gap <= limit and failed == 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    if traced:
        device.update(busy_s=ctx.busy_s, window_s=ctx.trace_window_s)
    result = {"correct": correct, "attempted": len(reqs) * len(reports),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks

    OUT.mkdir(exist_ok=True)
    (OUT / "last_run.json").write_text(json.dumps({
        "workload": workload, "seed": seed, "traced": traced,
        "weights_s": t_weights, "warm_replay_s": t_warm,
        "warm_programs": warm_programs, "warm_cache_hits": warm_hits,
        "replays": len(reports), "window_s": window_s, "ticks": ticks,
        "replay_ticks": [r.steps for r in reports],
        "tokens": [r.tokens for r in reports],
        "layer_s": ctx.layer_s, "check_s": t_check,
        "scored_tokens": scored["tokens"], "picks": picks,
        "result": result}, indent=1))
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    tamper = None
    if args.fault is not None:
        import faults
        if args.fault not in faults.FAULTS:
            raise SystemExit(f"bench: no fault {args.fault!r}; "
                             f"{sorted(faults.FAULTS)}")
        tamper = faults.FAULTS[args.fault]
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), tamper=tamper, control=args.control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
