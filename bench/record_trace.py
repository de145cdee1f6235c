#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench.py`` checks the trace
reducer against: one traced run of a cell's programs at two layers and
small widths, three requests, kept as ``testdata/small.xplane.pb``.

    python3 bench/record_trace.py       # on the chip
"""

import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

CELL = "qwen3-1.7b.doc_qa"
SMALL = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
         "vocab_size": 1024}
MIX = {"requests": 3, "arrival_every_ticks": 2, "length_seed": 1,
       "shared": {"tokens": 256, "askers": 3},
       "prompt": {"dist": "uniform", "min": 32, "max": 64},
       "answer": {"dist": "uniform", "min": 40, "max": 48}}


def main() -> int:
    import families
    cfg = families.load_config(run.cell_of(run.benchmark(), CELL)["config"])
    cfg = {**cfg, **SMALL, "engine": {**cfg["engine"], "max_len": 1024}}
    res = run.run_cell(CELL, 1, 0.0, True, config=cfg, mix=MIX,
                       limit=float("inf"))
    import trace_reduce as tr
    dest = HERE / "testdata" / "small.xplane.pb"
    dest.parent.mkdir(exist_ok=True)
    shutil.copy(tr.newest_xplane(run.OUT / "trace"), dest)
    print(res["metrics"], res["device"], dest.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
