"""From a profiler trace (``.xplane.pb``) to device intervals, program and
kernel times, idle gaps and the host activity behind them.

Planes named ``/device:TPU:<n>`` are the chips.  On each, the ``XLA
Modules`` line holds one event per program run and ``XLA Ops`` one event
per operation.  The host plane ``/host:CPU`` holds the benchmark's own
annotations (``bench.*``) and JAX's host events.  The window is the
``bench.window`` annotation.

Programs are named by the Python function that was jitted
(``jit_<name>(<id>)``).  ``PROGRAMS`` sorts them into the layers that the
per-layer metrics read.  Ops are named by their HLO text
(``%name = <shape> <opcode>(...)``); a container op (``while``, ``call``,
``conditional``) is listed together with the ops inside it.  A Pallas
kernel is a ``tpu_custom_call`` without its Python name: the decode step
has one, the page-table walk, and of the two lambdas that share the name
``jit__lambda`` the one holding a kernel is the page-mass scoring pass.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

# (layer, pattern on the module name without its "(id)")
PROGRAMS = (
    ("decode", r"jit_decode_step"),
    ("prefill", r"jit_prefill_step|jit_chunk_step"),
    ("plan", r"jit__plan_fn"),
    ("near_sync", r"jit_refresh_near_from_pool"),
    ("lambda", r"jit__lambda"),
)
KERNEL = 'custom_call_target="tpu_custom_call"'
CONTAINERS = ("while", "call", "conditional")
_OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z0-9-]*)\(")


@dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns


@dataclass
class Device:
    modules: list = field(default_factory=list)    # Event, sorted
    ops: list = field(default_factory=list)        # Event, sorted
    op_module: list = field(default_factory=list)  # index into modules


@dataclass
class Trace:
    devices: dict            # plane name -> Device
    host: list               # Event on the host plane, all threads
    window: tuple            # (start ns, end ns)


def newest_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line) -> list:
    names: dict = {}
    out = [Event(names.setdefault(e.name, e.name), float(e.start_ns),
                 float(e.start_ns + e.duration_ns)) for e in line.events]
    out.sort(key=lambda e: e.start)
    return out


def opcode(op_name: str) -> str:
    m = _OPCODE.search(op_name)
    return m.group(1) if m else ""


def short_op(op_name: str) -> str:
    """``%fusion.12 fusion``, ``%closed_call.3 custom-call`` ..."""
    return f"{op_name.split(' = ')[0]} {opcode(op_name)}".strip()


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = Device()
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = _events(line)
                elif line.name == "XLA Ops":
                    dev.ops = _events(line)
            dev.op_module = _owner(dev.modules, dev.ops)
            devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(_events(line))
    win = [e for e in host if e.name == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window annotation")
    return Trace(devices=devices, host=host, window=(win[0].start,
                                                     win[0].end))


def _owner(modules: list, ops: list) -> list:
    """For each op, the index of the module run that contains it (-1)."""
    if not modules:
        return [-1] * len(ops)
    starts = np.asarray([m.start for m in modules])
    ends = np.asarray([m.end for m in modules])
    t = np.asarray([op.start for op in ops])
    i = np.searchsorted(starts, t, side="right") - 1
    inside = (i >= 0) & (t < ends[np.maximum(i, 0)])
    return np.where(inside, i, -1).tolist()


def module_layer(name: str) -> str:
    base = name.split("(")[0]
    for layer, pat in PROGRAMS:
        if re.fullmatch(pat, base):
            return layer
    return "other"


def _clip(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end, hi) - max(e.start, lo))


def union(intervals: list, lo: float, hi: float) -> list:
    """Merged (start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which an op ran, averaged over chips."""
    lo, hi = tr.window
    per = [sum(e - s for s, e in union([(o.start, o.end) for o in d.ops],
                                       lo, hi))
           for d in tr.devices.values()]
    return float(np.mean(per)) / 1e9 if per else 0.0


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def _module_kind(dev: Device, i: int, op_names: dict) -> str:
    layer = module_layer(dev.modules[i].name)
    if layer == "lambda":
        return "masses" if any(KERNEL in n
                               for n in op_names.get(i, ())) else "meta"
    return layer


def layer_seconds(tr: Trace) -> dict:
    """Device seconds in the window per program kind, averaged over
    chips: decode, prefill, plan, near_sync, masses, meta, other."""
    lo, hi = tr.window
    tot: dict = {}
    for dev in tr.devices.values():
        op_names: dict = {}
        for op, m in zip(dev.ops, dev.op_module):
            op_names.setdefault(m, set()).add(op.name)
        for i, mod in enumerate(dev.modules):
            k = _module_kind(dev, i, op_names)
            tot[k] = tot.get(k, 0.0) + _clip(mod, lo, hi) / 1e9
    n = max(len(tr.devices), 1)
    return {k: v / n for k, v in tot.items()}


def op_seconds(tr: Trace, pattern: str, layer: str | None = None) -> float:
    """Device seconds in the window of ops whose name contains
    ``pattern`` (inside programs of ``layer`` if given), chip average."""
    lo, hi = tr.window
    tot = 0.0
    for dev in tr.devices.values():
        for op, m in zip(dev.ops, dev.op_module):
            if pattern not in op.name:
                continue
            if layer is not None and (m < 0 or module_layer(
                    dev.modules[m].name) != layer):
                continue
            tot += _clip(op, lo, hi)
    return tot / 1e9 / max(len(tr.devices), 1)


def top_ops(tr: Trace, n: int = 10) -> list:
    """The leaf ops that took most device time, named
    "<program>/<op> <opcode>"."""
    lo, hi = tr.window
    tot: dict = {}
    for dev in tr.devices.values():
        for op, m in zip(dev.ops, dev.op_module):
            if opcode(op.name) in CONTAINERS:
                continue
            prog = dev.modules[m].name.split("(")[0] if m >= 0 else "?"
            key = f"{prog}/{short_op(op.name)}"
            tot[key] = tot.get(key, 0.0) + _clip(op, lo, hi) / 1e9
    k = max(len(tr.devices), 1)
    return [[name, s / k] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """The longest gaps in which no op ran on the first chip, each named by
    the innermost host event that spans the gap's middle."""
    if not tr.devices:
        return []
    lo, hi = tr.window
    dev = tr.devices[sorted(tr.devices)[0]]
    busy = union([(o.start, o.end) for o in dev.ops], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        covering = [h for h in tr.host if h.start <= mid < h.end
                    and h.name != "bench.window"]
        label = min(covering, key=lambda h: h.end - h.start).name \
            if covering else "no host event"
        out.append([label, (e - s) / 1e9])
    return out
