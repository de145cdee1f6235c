#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/calibrate.py --workload qwen3-1.7b.doc_qa --seeds 1,2,3

For each seed, in one process and on one engine: the seed's weights and
requests, one whole replay of the cell's trace through the timed path, the
engine freed, then the sampled requests scored as ``run.py`` scores them,
through the configuration's family (``families/``): the widest gap of a
served token below the float32 reference's best (the program's reading),
and for each of the family's controls (dense: ``fp8``, every matmul input
and K/V row in float8, and ``fp8_kv``, the K/V rows alone) the widest gap
of the token that the control puts first.  One JSON line per seed, each
reading beside the cell's limit, then a summary line.  The limit lies
between the largest program reading and the smallest control reading
(``limits/<cell>.json``).
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)
    run.setup_jax()
    import correctness
    import families
    import hooks
    import workload as traffic

    bench = run.benchmark()
    cell = run.cell_of(bench, args.workload)
    run.chips(cell["chips"])
    cfg = families.load_config(cell["config"])
    fam = families.of(cfg)
    mix = traffic.load_mix(cell["traffic"])
    dm = fam.dims(cfg)
    limit = correctness.limits(args.workload)["served_logit_gap"]
    eng = probe = None
    lines = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        params = fam.program_params(seed, dm)
        if eng is None:
            eng = hooks.ServingEngine(params, fam.arch_config(cfg, dm),
                                      hooks.serving_config(cfg["engine"]))
            probe = hooks.EngineProbe(eng)
        else:
            hooks.set_params(eng, params)
        reqs = traffic.make_requests(mix, seed, dm.vocab)
        rep = probe.replay(hooks.requests(reqs))
        failed = correctness.failed_requests(reqs, rep.outputs, dm.vocab)
        hooks.free(eng)
        del params
        gc.collect()
        picks = correctness.sample(reqs, seed)
        got = correctness.served_gap(fam, seed, dm, reqs, rep.outputs, picks,
                                     fam.CONTROLS)
        line = {"seed": seed, "program_gap": got["gap"], "limit": limit,
                "program_within": got["gap"] <= limit}
        for c, g in got["controls"].items():
            line[f"{c}_gap"] = g
            line[f"{c}_within"] = g <= limit
        line.update(tokens=got["tokens"], failed=failed,
                    seconds=time.perf_counter() - t)
        lines.append(line)
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(lines),
        "program_gap_max": max(x["program_gap"] for x in lines),
        **{f"{c}_gap_min": min(x[f"{c}_gap"] for x in lines)
           for c in fam.CONTROLS},
        "limit": limit}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
