"""Benchmark driver: one section per paper table/figure plus the TPU-adapted
tiered-runtime benches and the roofline summary (if dry-run artifacts exist).

Output format: ``name,us_per_call,values...`` CSV per row.

  python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
from pathlib import Path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller request counts for CI")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import paper_figures, tiered_runtime_bench

    print("# --- paper figures/tables (TL-DRAM reproduction) ---")
    paper_figures.run_all(quick=args.quick)

    print("# --- tiered runtime (TPU adaptation, beyond-paper) ---")
    tiered_runtime_bench.run_all()

    art = Path("artifacts/dryrun")
    if art.exists() and any(art.glob("*.json")):
        print("# --- roofline (from multi-pod dry-run artifacts) ---")
        from repro.launch import roofline
        cells = roofline.load_cells(art, "single")
        for c in sorted(cells, key=lambda c: (c.arch, c.shape)):
            print(f"roofline,{c.arch},{c.shape},{c.compute_s*1e3:.2f}ms,"
                  f"{c.memory_s*1e3:.2f}ms,{c.collective_s*1e3:.2f}ms,"
                  f"{c.bound},{c.roofline_fraction:.3f}")
    else:
        print("# roofline: no dry-run artifacts (run repro.launch.dryrun)")


if __name__ == "__main__":
    main()
