#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, and the program and
op names with their device time.  For reading one trace by hand before
changing the name table in ``trace_reduce.py``.

    python3 bench/inspect_trace.py .bench_out/trace [--top 40]
"""

import argparse
import json
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce as tr  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    path = tr.newest_xplane(Path(args.trace_dir))
    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        lines = [(line.name, sum(1 for _ in line.events))
                 for line in plane.lines]
        print(json.dumps({"plane": plane.name, "lines": lines}))
    t = tr.load(path)
    for name, dev in t.devices.items():
        mods, ops = Counter(), Counter()
        for m in dev.modules:
            mods[m.name.split("(")[0]] += (m.end - m.start) / 1e9
        for op, i in zip(dev.ops, dev.op_module):
            prog = dev.modules[i].name.split("(")[0] if i >= 0 else "?"
            ops[f"{prog}/{op.name}"] += (op.end - op.start) / 1e9
        print(json.dumps({"device": name, "programs": mods.most_common(),
                          "ops": ops.most_common(args.top)}))
    host = Counter(e.name for e in t.host)
    print(json.dumps({"host_events": host.most_common(args.top),
                      "window_s": tr.window_s(t), "busy_s": tr.busy_s(t),
                      "layer_s": tr.layer_seconds(t),
                      "idle_gaps": tr.idle_gaps(t)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
