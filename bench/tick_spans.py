#!/usr/bin/env python3
"""The serving engine's own spans against the first chip's ops, in the
traced window: where the tick loop spends host time, and which engine code
the chip sat idle behind.

    python3 bench/tick_spans.py .bench_out/trace [--gaps 10]

The engine writes constant-named spans on the profiler's host plane
(``repro.serve.engine``): ``serve.run`` holds ``serve.setup``, one
``serve.tick`` per loop iteration and ``serve.teardown``; a tick holds
``serve.admit``, ``serve.prefill``, ``serve.decode`` and ``serve.maintain``;
``serve.wait.<what>`` spans each blocking read of a device value.  The
idle time of the window splits without remainder into: inside ticks and
outside waits (the chip waiting on engine host code), inside waits (the
round trip a read exposes), and outside ticks (``serve.setup``,
``serve.teardown`` or no ``serve.*`` span).  A trace without the spans
gives no account.  The tick-loop metrics read ``account``; run as a
script it prints the account and the longest idle gaps, each named by the
innermost ``serve.*`` span around it.
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
from collections import Counter
from dataclasses import asdict, dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce as tr  # noqa: E402

TICK, DECODE, WAIT = "serve.tick", "serve.decode", "serve.wait."
OUTSIDE = ("serve.setup", "serve.teardown")


def measure(iv: list) -> float:
    return sum(e - s for s, e in iv)


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """``a`` less ``b``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append([t, b[k][0]])
            t = max(t, b[k][1])
            k += 1
        if e > t:
            out.append([t, e])
    return out


def spans(trace, match) -> list:
    """Union of the host events whose name ``match`` accepts, clipped to
    the window."""
    lo, hi = trace.window
    return tr.union([(h.start, h.end) for h in trace.host if match(h.name)],
                    lo, hi)


def idle(trace) -> list:
    """Intervals of the window in which no op ran on the first chip."""
    lo, hi = trace.window
    dev = trace.devices[sorted(trace.devices)[0]]
    out, t = [], lo
    for s, e in tr.union([(o.start, o.end) for o in dev.ops], lo, hi):
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if hi > t:
        out.append([t, hi])
    return out


@dataclass
class Account:
    """Seconds of the window, and the decode ticks that began in it."""
    window_s: float
    decode_ticks: int
    tick_s: float              # inside serve.tick
    tick_wait_s: float         # inside serve.tick and serve.wait.*
    idle_s: float              # no op on the first chip
    idle_host_s: float         # idle, inside a tick, outside every wait
    idle_wait_s: float         # idle, inside serve.wait.*
    idle_setup_s: float        # idle outside ticks, inside serve.setup
    idle_teardown_s: float     # idle outside ticks, inside serve.teardown
    idle_other_s: float        # idle outside ticks and both of those
    waits: dict                # serve.wait.<what> -> events in the window


def account(trace) -> Account | None:
    if trace is None or not trace.devices:
        return None
    lo, hi = trace.window
    ticks = spans(trace, lambda n: n == TICK)
    n_decode = sum(1 for h in trace.host
                   if h.name == DECODE and lo <= h.start < hi)
    if not ticks or not n_decode:
        return None
    waits = spans(trace, lambda n: n.startswith(WAIT))
    gaps = idle(trace)
    outside = subtract(gaps, ticks)
    part = {n: measure(intersect(outside, spans(trace, n.__eq__)))
            for n in OUTSIDE}
    ns = 1e-9
    return Account(
        window_s=(hi - lo) * ns, decode_ticks=n_decode,
        tick_s=measure(ticks) * ns,
        tick_wait_s=measure(intersect(ticks, waits)) * ns,
        idle_s=measure(gaps) * ns,
        idle_host_s=measure(subtract(intersect(gaps, ticks), waits)) * ns,
        idle_wait_s=measure(intersect(gaps, waits)) * ns,
        idle_setup_s=part["serve.setup"] * ns,
        idle_teardown_s=part["serve.teardown"] * ns,
        idle_other_s=(measure(outside) - sum(part.values())) * ns,
        waits=dict(Counter(h.name for h in trace.host
                           if h.name.startswith(WAIT)
                           and lo <= h.start < hi)))


def named_gaps(trace, n: int = 10) -> list:
    """The ``n`` longest idle gaps: seconds, the innermost ``serve.*`` span
    and the innermost host event of any name over the gap's middle, and
    how many ticks of its ``serve.run`` had begun by then."""
    runs = sorted((h.start, h.end) for h in trace.host
                  if h.name == "serve.run")
    ticks = sorted(h.start for h in trace.host if h.name == TICK)
    out = []
    for s, e in sorted(idle(trace), key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        over = [h for h in trace.host if h.start <= mid < h.end
                and h.name != "bench.window"]
        inner = lambda hs: min(hs, key=lambda h: h.end - h.start).name \
            if hs else None
        run = [a for a, b in runs if a <= mid < b]
        out.append({"s": (e - s) * 1e-9,
                    "serve": inner([h for h in over
                                    if h.name.startswith("serve.")]),
                    "host": inner(over),
                    "run_tick": bisect.bisect(ticks, mid)
                    - bisect.bisect(ticks, run[0]) if run else None})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--gaps", type=int, default=10)
    args = ap.parse_args(argv)
    trace = tr.load(tr.newest_xplane(Path(args.trace_dir)))
    acc = account(trace)
    print(json.dumps({"account": acc and asdict(acc),
                      "gaps": named_gaps(trace, args.gaps)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
