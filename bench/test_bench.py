"""CPU checks of the benchmark itself, at reduced widths.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_bench.py

* the float32 reference agrees with the serving program on what it
  served, and the float8 control does not;
* a run with the timed path broken underneath comes out ``correct: false``
  for each fault a serving cell can have (``faults.py``): a decode step
  that leaves its state (the K/V pool) unchanged, and a token altered
  where it is made; so does a run with the float8 control scored in the
  program's place;
* the trace reducer reads the small chip trace kept in ``testdata/``;
* the traffic generator keeps lengths fixed across seeds, and takes a mix
  written as a module;
* ``BENCHMARK.json`` keeps to the names and keys the harness relies on.

These tests skip the harness's look for a chip (``require_tpu=False``).
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import correctness  # noqa: E402
import faults  # noqa: E402
import model  # noqa: E402
import run  # noqa: E402
import workload  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512}
TINY_MIX = {"requests": 6, "arrival_every_ticks": 2, "length_seed": 3,
            "prompt": {"dist": "uniform", "min": 40, "max": 200},
            "answer": {"dist": "uniform", "min": 24, "max": 48}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# Readings at these sizes (CPU, interpret-mode kernels): the program's
# widest gap stays at bf16 rounding; the float8 control's lies far above.
TINY_LIMIT = 0.05
CELLS = ["qwen3-1.7b.doc_qa", "yi-9b.chat"]


def tiny(cell: str, **engine):
    c = run.cell_of(run.benchmark(), cell)
    cfg = model.load_config(c["config"])
    cfg = {**cfg, **TINY, "engine": {**cfg["engine"], "max_len": 512,
                                     **engine}}
    mix = dict(TINY_MIX)
    if workload.load_mix(c["traffic"]).get("shared"):
        mix["shared"] = {"tokens": 128, "askers": 3}
    return cfg, mix


def run_tiny(cell, seed=2**31 + 17, tamper=None, control=None, **engine):
    cfg, mix = tiny(cell, **engine)
    return run.run_cell(cell, seed, 0.0, False, require_tpu=False,
                        config=cfg, mix=mix, tamper=tamper, peaks=PEAKS,
                        limit=TINY_LIMIT, control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_and_control_does_not(cell):
    """The program's served tokens sit within bf16 rounding of the
    reference's best; the float8 control's picks do not."""
    cfg, mix = tiny(cell)
    dm = model.dims(cfg)
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    gap = res["checks"]["served_logit_gap"]["value"]
    reqs = workload.make_requests(mix, 2**31 + 17, dm.vocab)
    # the control reads the same sequences: prompt + the reference's own
    # greedy tokens stand in for what was served
    outputs = {r["rid"]: _greedy(dm, 2**31 + 17, r) for r in reqs}
    picks = correctness.sample(reqs, 2**31 + 17)
    got = correctness.served_gap(2**31 + 17, dm, reqs, outputs, picks,
                                 ("fp8",))
    assert got["gap"] == 0.0
    assert got["controls"]["fp8"] > TINY_LIMIT > gap, (got, gap)


def _greedy(dm, seed, r):
    import jax.numpy as jnp
    import reference
    w = model.make_weights(seed, dm)
    seq = list(r["prompt"])
    for _ in range(r["max_new_tokens"]):
        tokens = jnp.asarray(np.asarray(seq, np.int32))
        pad = -(-len(seq) // reference.PAD) * reference.PAD
        tokens = jnp.pad(tokens, (0, pad - len(seq)))
        idx = jnp.full((reference.HEAD_PAD,), len(seq) - 1, jnp.int32)
        seq.append(int(jnp.argmax(reference.logits_at(w, tokens, idx,
                                                      dm)[0])))
    return seq[len(r["prompt"]):]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    res = run_tiny(cell, tamper=faults.FAULTS[fault])
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["served_logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell):
    res = run_tiny(cell, control="fp8")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["served_logit_gap"]["value"] > TINY_LIMIT


def test_no_tpu_no_result():
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit):
        run.chips(1)


def test_reducer_reads_small_chip_trace():
    import trace_reduce as tr
    path = HERE / "testdata" / "small.xplane.pb"
    t = tr.load(path)
    assert t.devices, "no TPU plane in the recorded trace"
    busy, win = tr.busy_s(t), tr.window_s(t)
    assert 0 < busy <= win
    layers = tr.layer_seconds(t)
    for k in ("decode", "prefill", "meta"):
        assert layers.get(k, 0) > 0, layers
    walk = tr.op_seconds(t, tr.KERNEL, layer="decode")
    assert 0 < walk < layers["decode"]
    gaps = tr.idle_gaps(t)
    assert gaps and sum(g for _, g in gaps) <= win - busy + 1e-9
    assert len(tr.top_ops(t)) == 10


def test_lengths_fixed_across_seeds_tokens_not():
    for name in ("doc_qa", "chat"):
        mix = workload.load_mix(name)
        a = workload.make_requests(mix, 1, 1000)
        b = workload.make_requests(mix, 2**31 + 5, 1000)
        assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
        assert [r["max_new_tokens"] for r in a] == \
            [r["max_new_tokens"] for r in b]
        assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    mix = workload.load_mix("doc_qa")
    reqs = workload.make_requests(mix, 3, 1000)
    n = mix["shared"]["tokens"]
    assert (reqs[0]["prompt"][:n] == reqs[3]["prompt"][:n]).all()
    assert (reqs[0]["prompt"][:n] != reqs[4]["prompt"][:n]).any()


def test_mix_written_as_a_module(tmp_path, monkeypatch):
    (tmp_path / "turns.py").write_text(
        "import numpy as np\n"
        "def make_requests(seed, vocab):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    return [{'rid': i, 'arrival': 3 * i, 'max_new_tokens': 8,\n"
        "             'prompt': rng.integers(0, vocab, 20, dtype=np.int32)}\n"
        "            for i in range(4)]\n")
    (tmp_path / "turns.json").write_text("{}")
    monkeypatch.setattr(workload, "TRAFFIC_DIR", tmp_path)
    reqs = workload.make_requests(workload.load_mix("turns"), 5, 100)
    assert [r["arrival"] for r in reqs] == [0, 3, 6, 9]
    assert all(len(r["prompt"]) == 20 for r in reqs)


def test_longest_answer_first_keeps_the_drawn_lengths():
    mix = workload.load_mix("chat")
    mixed = {k: v for k, v in mix.items() if k != "order"}
    a = workload.make_requests(mix, 9, 1000)
    b = workload.make_requests(mixed, 9, 1000)
    served = [r["max_new_tokens"] for r in a]
    assert served == sorted(served, reverse=True)
    assert sorted(served) == sorted(r["max_new_tokens"] for r in b)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]


NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_manifest():
    b = run.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.fullmatch(n) for n in names)
    assert len(set(names)) == len(names)
    for c in b["configs"]:
        assert (run.ROOT / c["file"]).is_file()
    for w in b["workloads"]:
        assert any((HERE / "traffic" / f"{w['traffic']}{ext}").is_file()
                   for ext in (".json", ".py"))
        assert (HERE / "limits" / f"{w['name']}.json").is_file()
    for m in b["end_to_end"] + b["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    layers = {m["layer"] for m in b["per_layer"]}
    assert all(m["moves"] in {e["name"] for e in b["end_to_end"]}
               for m in b["per_layer"]), layers
    assert json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
