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
* the ``dense`` family computes, to the bit, what the harness computed
  before the families held the architecture, and a run reaches the
  architecture through the configuration's family alone
  (``testdata/counting.py``);
* the traffic generator keeps lengths fixed across seeds, and takes a mix
  written as a module;
* ``BENCHMARK.json`` keeps to the names and keys the harness relies on.

These tests skip the harness's look for a chip (``require_tpu=False``).
"""

from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import correctness  # noqa: E402
import faults  # noqa: E402
import families  # noqa: E402
import hooks  # noqa: E402
import run  # noqa: E402
import workload  # noqa: E402
from families import dense, dense_reference  # noqa: E402

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
    cfg = families.load_config(c["config"])
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
    dm = dense.dims(cfg)
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    gap = res["checks"]["served_logit_gap"]["value"]
    reqs = workload.make_requests(mix, 2**31 + 17, dm.vocab)
    # the control reads the same sequences: prompt + the reference's own
    # greedy tokens stand in for what was served
    outputs = {r["rid"]: _greedy(dm, 2**31 + 17, r) for r in reqs}
    picks = correctness.sample(reqs, 2**31 + 17)
    got = correctness.served_gap(dense, 2**31 + 17, dm, reqs, outputs,
                                 picks, ("fp8",))
    assert got["gap"] == 0.0
    assert got["controls"]["fp8"] > TINY_LIMIT > gap, (got, gap)


def _greedy(dm, seed, r):
    import jax.numpy as jnp
    ref = dense_reference
    w = dense.make_weights(seed, dm)
    seq = list(r["prompt"])
    for _ in range(r["max_new_tokens"]):
        tokens = jnp.asarray(np.asarray(seq, np.int32))
        pad = -(-len(seq) // ref.PAD) * ref.PAD
        tokens = jnp.pad(tokens, (0, pad - len(seq)))
        idx = jnp.full((ref.HEAD_PAD,), len(seq) - 1, jnp.int32)
        seq.append(int(jnp.argmax(ref.logits_at(w, tokens, idx, dm)[0])))
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


# Integers from the formulas as they stood before the families held the
# architecture (commit ecf5bf2: ``model.Dims`` and ``model.token_flops``,
# ``hooks.pool_bytes``, ``metrics/decode_roofline.step_bytes`` and the
# sums in ``metrics/step_mfu.py`` and ``metrics/walk_roofline.py``), run
# once at these (active slots, live rows) pairs.  ``token_flops`` is at
# (context 0, head), (0, no head), (3072, head); ``pool_bytes`` at 3,072
# pages of 16 rows; ``window_flops`` is step_mfu's sum over the three pairs
# as decode calls, prefill spans (0, 512) and (3072, 3200), and 5 requests.
PAIRS = [(1, 1), (7, 23113), (8, 32768)]
SPANS = [(0, 512), (3072, 3200)]
GOLDEN = {
    "qwen3-1.7b": {
        "dims": "Dims(name='qwen3-1.7b', vocab=151936, d=2048, f=6144, "
                "layers=28, heads=16, kv_heads=8, hd=128, qk_norm=True, "
                "tied=True, rope_theta=1000000.0, eps=1e-06)",
        "step_bytes": [3441375232, 6092732416, 7200159744],
        "walk_bytes": [114688, 2650783744, 3758096384],
        "token_flops": [3440902144, 2818572288, 4145545216],
        "pool_bytes": 5637144576,
        "window_flops": 1997081870336},
    "yi-9b": {
        "dims": "Dims(name='yi-9b', vocab=64000, d=4096, f=11008, "
                "layers=24, heads=32, kv_heads=4, hd=128, qk_norm=False, "
                "tied=False, rope_theta=5000000.0, eps=1e-06)",
        "step_bytes": [8829501440, 9965797376, 10440409088],
        "walk_bytes": [49152, 1136050176, 1610612736],
        "token_flops": [8829009920, 8304721920, 10036969472],
        "pool_bytes": 2415919104,
        "window_flops": 5690386808832},
}
# ``testdata/reference_bits.py``'s digests of the same code's output at
# TINY widths, seed 2**31 + 17 (the weights in both layouts; the
# reference's logits, plain and under each control; ``gaps``), as the code
# before the families gave them on one core.
GOLDEN_BITS = {
    "qwen3-1.7b": {
        "weights": "15a9c1094c817f4b", "program_weights": "d78b46333b90359a",
        "logits": "0a294807ce31d9bd", "logits_fp8": "e1fb4693e9308ddd",
        "logits_fp8_kv": "dc2cf693adf4e932", "gaps": "44717a603a26c836"},
    "yi-9b": {
        "weights": "6309c600cf10fb6a", "program_weights": "50de56165f0900f4",
        "logits": "0db970d199c9c8a4", "logits_fp8": "e3044052b49b6707",
        "logits_fp8_kv": "53eed065b1a78b31", "gaps": "2cfdc79a290ec556"},
}


def _call(active, live):
    """A decode call with ``live`` rows spread over ``active`` slots."""
    rows = [live // active] * active
    rows[0] += live - sum(rows)
    return hooks.DecodeCall(active, live, tuple(rows))


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_dense_accounting_matches_the_formulas_before_families(name):
    g = GOLDEN[name]
    dm = dense.dims(families.load_config(name))
    assert repr(dm) == g["dims"]
    assert [dense.decode_bytes(dm, [_call(a, l)], []) for a, l in PAIRS] \
        == g["step_bytes"]
    assert [dense.walk_bytes(dm, [_call(a, l)], []) for a, l in PAIRS] \
        == g["walk_bytes"]
    assert [dense.token_flops(dm, c, h) for c, h in
            ((0, True), (0, False), (3072, True))] == g["token_flops"]
    eng = SimpleNamespace(pool_pages=3072,
                          cfg=SimpleNamespace(tier=SimpleNamespace(page=16)))
    assert hooks.pool_bytes(eng, dense.pool_row_bytes(dm)) == g["pool_bytes"]
    reports = [SimpleNamespace(outputs=dict.fromkeys(range(3))),
               SimpleNamespace(outputs=dict.fromkeys(range(2)))]
    calls = [_call(a, l) for a, l in PAIRS]
    assert dense.flops(dm, calls, SPANS, reports) == g["window_flops"]
    assert dense.decode_bytes(dm, calls, reports) == sum(g["step_bytes"])
    assert dense.walk_bytes(dm, calls, reports) == sum(g["walk_bytes"])


@pytest.mark.parametrize("name", sorted(GOLDEN_BITS))
def test_dense_weights_and_reference_are_bit_identical(name):
    out = subprocess.run(
        [sys.executable, str(HERE / "testdata" / "reference_bits.py"), name,
         str(2**31 + 17), json.dumps(TINY)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, check=True)
    assert json.loads(out.stdout.splitlines()[-1]) == GOLDEN_BITS[name]


def test_family_seam_is_the_only_route(monkeypatch):
    """A run of a configuration that names the test-only ``counting``
    family (``testdata/counting.py``, ``dense`` with every call counted)
    makes every weight, reference and accounting call through it, and
    reads exactly what the ``dense`` family's run reads.  The traced
    window's reduction reads the small chip trace, so that the three
    accounting readers have device time to divide by."""
    import jax
    import trace_reduce
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(HERE / "testdata")])
    monkeypatch.setattr(trace_reduce, "newest_xplane",
                        lambda _: HERE / "testdata" / "small.xplane.pb")
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    cell = CELLS[0]
    cfg, mix = tiny(cell)

    def once(family):
        return run.run_cell(cell, 2**31 + 17, 0.0, True, require_tpu=False,
                            config={**cfg, "family": family}, mix=mix,
                            peaks=PEAKS, limit=TINY_LIMIT)

    want = once("dense")
    counting = importlib.import_module("families.counting")
    counting.CALLS.clear()
    got = once("counting")
    assert set(counting.CALLS) == {k for k in families.PROVIDES
                                   if callable(getattr(dense, k))}
    for m in ("decode_roofline", "step_mfu", "walk_roofline"):
        assert got["metrics"][m]["value"] > 0
    assert got == want
    # and no file of the harness names a family of its own accord
    names_one = re.compile(r"families\.dense|import dense|['\"]dense['\"]")
    harness = [HERE / f for f in ("run.py", "correctness.py", "calibrate.py",
                                  "record_trace.py", "hooks.py")]
    for f in harness + sorted((HERE / "metrics").glob("*.py")):
        assert not names_one.search(f.read_text()), f


def test_a_missing_family_stops_the_run():
    cfg, mix = tiny(CELLS[0])
    for bad, said in (({**cfg, "family": "no_such"}, "'no_such'"),
                      ({k: v for k, v in cfg.items() if k != "family"},
                       "names no family")):
        with pytest.raises(SystemExit, match=said):
            run.run_cell(CELLS[0], 1, 0.0, False, require_tpu=False,
                         config=bad, mix=mix, peaks=PEAKS, limit=TINY_LIMIT)


def test_probe_keeps_each_slots_rows():
    """``EngineProbe`` notes each active slot's live rows beside their sum,
    from the engine's host mirror of ``pos``."""
    eng = SimpleNamespace(run=None, _decode=lambda *a: a,
                          _advance_prefills=None,
                          slots=[None, "a", None, "b", "c"],
                          pos=np.array([9, 4, 0, 17, 2]))
    probe = hooks.EngineProbe(eng)
    probe.decode(1)
    eng.slots[1] = None
    eng.pos = eng.pos + 1
    probe.decode(2)
    assert probe.calls() == [hooks.DecodeCall(3, 26, (5, 18, 3)),
                             hooks.DecodeCall(2, 23, (19, 4))]
    assert probe.live == [26, 23] and probe.active == [3, 2]


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
