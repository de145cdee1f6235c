"""repro-lint invariant engine (ISSUE 7 tentpole).

Red-first coverage: every shipped pass must fire on a deliberately broken
mini-step (dense mask tensor, bf16-accumulated read dot, host callback in a
tick, over-budget gather, unpaired pin / denied API / tick host pull) and
stay green on the real serving stack under the committed baseline.  Plus:
walker nesting uniformity (the bug class the old per-test private walkers
had), baseline key stability / staleness reporting, and the CLI exit
contract.
"""

import json
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import walker
from repro.analysis.ownership import lint_ownership
from repro.analysis.passes import (f32_accumulation, no_collectives,
                                   no_dense_far_view, no_host_sync,
                                   vmem_budget)
from repro.analysis.report import AnalysisReport, Violation, violation_key
from repro.analysis.runner import run_analysis
from repro.analysis.targets import AnalysisTarget, ForbiddenShape

B, N_PAGES, C = 5, 7, 3
Hkv, hd = 4, 64


def _target(fn, args, **kw):
    return AnalysisTarget(name="mini", fn=fn, args=args, **kw)


class TestWalker:
    def test_collects_through_nested_scan_pjit(self):
        """One traversal surfaces equations at every nesting depth — the
        uniformity the old per-test walkers re-implemented case by case."""
        def f(x):
            def body(c, xi):
                return c + jnp.sin(xi).sum(), None   # sin inside scan
            out, _ = jax.lax.scan(body, 0.0, x)
            return jax.jit(jnp.cos)(out)             # cos inside jit
        walked = walker.collect_eqns(jax.make_jaxpr(f)(jnp.ones((3, 4))))
        prims = {(we.prim, we.path) for we in walked}
        assert ("sin", ("scan",)) in prims
        assert ("cos", ("jit",)) in prims

    def test_intermediate_shapes_spans_depths(self):
        def f(x):
            def body(c, xi):
                return c, jnp.outer(xi, xi)          # (4,4) only in the scan
            _, ys = jax.lax.scan(body, 0.0, x)
            return ys
        shapes = walker.intermediate_shapes(jax.make_jaxpr(f)(jnp.ones((3, 4))))
        assert (4, 4) in shapes

    def test_taint_survives_layout_ops_and_dies_at_arithmetic(self):
        def f(kv, x):
            k = kv.reshape(6, 4).T                  # layout: stays RAW
            s = x @ k                                # dot with RAW operand
            return s @ jnp.ones((6, 2))              # dot on DERIVED only
        walked = walker.collect_eqns(
            jax.make_jaxpr(f)(jnp.ones((4, 6)), jnp.ones((2, 4))),
            kv_invars=[0])
        dots = [we for we in walked if we.prim == "dot_general"]
        assert walker.TAINT_RAW in dots[0].in_taints
        assert walker.TAINT_RAW not in dots[1].in_taints

    def test_taint_flows_through_call_primitives(self):
        """jit/scan outputs inherit their sub-jaxpr's taint: a padded /
        scanned KV buffer is still raw KV (the suffix-prefill shape)."""
        def f(kv, q):
            kp = jnp.pad(kv, ((0, 2), (0, 0)))       # jit-wrapped pad

            def body(c, row):
                return c + (q @ row), None           # row is scanned raw KV
            out, _ = jax.lax.scan(body, jnp.zeros((3,)), kp)
            return out
        walked = walker.collect_eqns(
            jax.make_jaxpr(f)(jnp.ones((4, 5)), jnp.ones((3, 5))),
            kv_invars=[0])
        dots = [we for we in walked if we.prim == "dot_general"]
        assert dots and all(walker.TAINT_RAW in d.in_taints for d in dots)

    def test_hlo_ops_present_matches_instructions_not_metadata(self):
        hlo = ("ENTRY %main (p0: f32[8]) -> f32[8] {\n"
               "  %ar = f32[8] all-reduce(%p0), replica_groups={{0,1}}\n"
               "  ROOT %r = f32[8] add(%ar, %ar), metadata={op_name=\""
               "all-gather-ish\"}\n}")
        assert walker.hlo_ops_present(hlo, walker.COLLECTIVE_OPS) == \
            ["all-reduce"]


class TestPlantedViolations:
    """Each pass must flag its deliberately broken mini-step (red) and not
    flag the compliant twin (green)."""

    def test_dense_mask_tensor_fires(self):
        def bad(pt, sop):
            eq = pt[:, :, None] == sop[None, None, :]   # (B, n_pages, C)
            return eq.sum()
        t = _target(bad, (jnp.zeros((B, N_PAGES), jnp.int32),
                          jnp.zeros((C,), jnp.int32)),
                    forbidden_shapes=(ForbiddenShape(
                        (B, N_PAGES, C), "b-npages-c", "planted"),))
        v = no_dense_far_view(t)
        assert len(v) == 1 and v[0].rule == "b-npages-c"

    def test_hoisted_metadata_is_clean(self):
        def ok(pt, lengths):
            return (pt >= 0).sum() + lengths.sum()
        t = _target(ok, (jnp.zeros((B, N_PAGES), jnp.int32),
                         jnp.zeros((B,), jnp.int32)),
                    forbidden_shapes=(ForbiddenShape(
                        (B, N_PAGES, C), "b-npages-c", "planted"),))
        assert no_dense_far_view(t) == []

    def test_bf16_accumulated_read_dot_fires(self):
        def bad(q, pool_k):
            k = pool_k.reshape(-1, Hkv, hd)
            return jnp.einsum("bkd,tkd->bkt", q, k)      # bf16 out, no cast
        t = _target(bad, (jnp.zeros((B, Hkv, hd), jnp.bfloat16),
                          jnp.zeros((37, 8, Hkv, hd), jnp.bfloat16)),
                    kv_args=(1,))
        v = f32_accumulation(t)
        assert len(v) == 1 and "bfloat16" in v[0].detail

    @pytest.mark.parametrize("style", ["preferred", "cast"])
    def test_f32_accumulation_idioms_are_clean(self, style):
        def ok(q, pool_k):
            k = pool_k.reshape(-1, Hkv, hd)
            if style == "preferred":
                return jnp.einsum("bkd,tkd->bkt", q, k,
                                  preferred_element_type=jnp.float32)
            return jnp.einsum("bkd,tkd->bkt", q, k).astype(jnp.float32)
        t = _target(ok, (jnp.zeros((B, Hkv, hd), jnp.bfloat16),
                         jnp.zeros((37, 8, Hkv, hd), jnp.bfloat16)),
                    kv_args=(1,))
        assert f32_accumulation(t) == []

    def test_network_dot_is_exempt(self):
        """A bf16 dot on DERIVED values (attention out @ w_o) is network
        compute, not the read path — the taint lattice must exempt it."""
        def ok(q, pool_k, wo):
            k = pool_k.reshape(-1, Hkv, hd)
            s = jnp.einsum("bkd,tkd->bkt", q, k,
                           preferred_element_type=jnp.float32)
            out = s.astype(jnp.bfloat16).sum(-1)         # (B, Hkv): derived
            return jnp.einsum("bk,km->bm", out, wo)      # bf16 net dot: ok
        t = _target(ok, (jnp.zeros((B, Hkv, hd), jnp.bfloat16),
                         jnp.zeros((37, 8, Hkv, hd), jnp.bfloat16),
                         jnp.zeros((Hkv, 8), jnp.bfloat16)),
                    kv_args=(1,))
        assert f32_accumulation(t) == []

    def test_host_callback_in_tick_fires(self):
        def bad(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        t = _target(bad, (jnp.zeros((4,)),))
        v = no_host_sync(t)
        assert len(v) == 1 and "pure_callback" in v[0].detail

    def test_non_tick_target_is_not_checked(self):
        def bad(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        t = _target(bad, (jnp.zeros((4,)),), per_tick=False)
        from repro.analysis.passes import PASSES
        p = next(p for p in PASSES if p.name == "no-host-sync")
        assert not p.applies(t)

    def test_over_budget_gather_fires(self):
        """Traced over ShapeDtypeStructs — the 256 MiB far view is priced
        statically, never allocated."""
        pool = jax.ShapeDtypeStruct((100000, 8, Hkv, hd), jnp.bfloat16)
        idx = jax.ShapeDtypeStruct((4, 16384), jnp.int32)

        def bad(pool, i):
            return pool[i].sum()                   # (4,16384,8,Hkv,hd) bf16
        v = vmem_budget(_target(bad, (pool, idx)))
        assert v and all(x.rule == "oversized-intermediate" for x in v)
        assert any("gather" in x.detail for x in v)

    def test_within_budget_gather_is_clean(self):
        pool = jax.ShapeDtypeStruct((370, 8, Hkv, hd), jnp.bfloat16)
        idx = jax.ShapeDtypeStruct((4, 64), jnp.int32)
        assert vmem_budget(_target(lambda p, i: p[i].sum(),
                                   (pool, idx))) == []

    def test_planted_collective_fires(self):
        """no-collectives detection on an HLO module with a real collective
        (synthetic text — single-host CPU lowering cannot emit one)."""
        class Fake(AnalysisTarget):
            def walk(self):
                return []                 # no jaxpr collectives to excuse it
            def hlo_text(self):
                return ("ENTRY %e (p0: f32[4]) -> f32[4] {\n"
                        "  ROOT %ar = f32[4] all-reduce(%p0)\n}")
        t = Fake(name="fake", fn=None, args=(), check_collectives=True)
        v = no_collectives(t)
        assert len(v) == 1 and v[0].rule == "collective-op" \
            and "all-reduce" in v[0].detail

    def _rogue_axis_target(self, **kw):
        """A shard_map collective over a 1-device mesh whose axis name is
        NOT declared anywhere — runs on any host, no forced devices."""
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.asarray(jax.devices()[:1]), ("rogue",))

        def stepish(x):
            return jax.shard_map(lambda xs: jax.lax.psum(xs, "rogue"),
                                 mesh=mesh, in_specs=(P(),), out_specs=P(),
                                 check_vma=False)(x)
        return _target(stepish, (jnp.ones((4,)),), check_collectives=True,
                       **kw)

    def test_undeclared_axis_collective_fires(self):
        """The mesh-sharding escape hatch must not be a blank check: a
        jaxpr collective over an axis the target has NOT declared is
        flagged even though the op kind (all-reduce) could be declared
        for some other axis."""
        v = no_collectives(self._rogue_axis_target())
        assert any(x.rule == "collective-axis" and "rogue" in x.detail
                   for x in v)

    def test_declared_axis_collective_is_clean(self):
        """The same collective with its axis declared passes both layers:
        the jaxpr check (axis allowed) and the HLO check (the all-reduce
        kind is accounted for by the declared psum)."""
        assert no_collectives(
            self._rogue_axis_target(allowed_axes=("rogue",))) == []

    def test_allowed_axes_merge_from_baseline_through_runner(self):
        """run_analysis merges the baseline file's ``allowed_axes`` into
        targets by name — the declaration is committed config, not a code
        default."""
        t = self._rogue_axis_target()
        rep = run_analysis(mode="dense", targets=[t], with_ownership=False,
                           baseline={},
                           allowed_axes={"mini": ["rogue"]})
        assert rep.ok and t.allowed_axes == ("rogue",)
        t2 = self._rogue_axis_target()
        rep2 = run_analysis(mode="dense", targets=[t2],
                            with_ownership=False, baseline={})
        assert not rep2.ok and \
            rep2.active[0].rule == "collective-axis"


class TestOwnershipLinter:
    def _lint(self, tmp_path, source, name="mod.py"):
        (tmp_path / name).write_text(textwrap.dedent(source))
        return lint_ownership(tmp_path)

    def test_unpaired_alloc_fires(self, tmp_path):
        v = self._lint(tmp_path, """
            def admit(pool):
                pages = pool.allocate(4)      # never released anywhere
                return pages
        """)
        assert any(x.rule == "unpaired-ref" and "allocate" in x.detail
                   for x in v)

    def test_paired_alloc_is_clean(self, tmp_path):
        v = self._lint(tmp_path, """
            def admit(pool):
                return pool.allocate(4)

            def retire(pool, pages):
                pool.release(pages)
        """)
        assert not [x for x in v if x.rule == "unpaired-ref"]

    def test_unpaired_pin_fires(self, tmp_path):
        v = self._lint(tmp_path, """
            from repro.core import tiered_kv as tkv

            def maintain(tier, pages, slots, cfg):
                return tkv.paged_pin_pages(tier, pages, slots, cfg)
        """)
        assert any(x.rule == "unpaired-ref"
                   and "paged_pin_pages" in x.detail for x in v)

    def test_tick_host_pull_fires(self, tmp_path):
        v = self._lint(tmp_path, """
            import numpy as np

            class ServingEngine:
                def run(self, trace):
                    toks = np.asarray(self.logits)    # host pull per token
                    return toks

                def _admit(self, req):
                    return np.asarray(req.prompt)     # boundary: exempt
        """)
        pulls = [x for x in v if x.rule == "tick-host-pull"]
        assert len(pulls) == 1 and "ServingEngine.run" in pulls[0].where

    def test_block_until_ready_fires(self, tmp_path):
        v = self._lint(tmp_path, """
            class ServingEngine:
                def _maintain(self, x):
                    return x.block_until_ready()
        """)
        assert any(x.rule == "tick-host-pull"
                   and "block_until_ready" in x.detail for x in v)

    def test_real_src_has_no_unwaived_findings(self):
        src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
        v = lint_ownership(src)
        assert not [x for x in v if x.rule in ("deny-list", "unpaired-ref",
                                               "syntax-error")]
        # tick host pulls exist but every one is waived by the baseline
        from repro.analysis.report import load_baseline
        from repro.analysis.runner import DEFAULT_BASELINE
        waivers = load_baseline(DEFAULT_BASELINE)
        pulls = [x for x in v if x.rule == "tick-host-pull"]
        assert pulls, "expected the engine's known host-pull sites"
        unwaived = [x.key for x in pulls if x.key not in waivers]
        assert not unwaived, f"new unwaived host pulls: {unwaived}"


class TestBaselineMechanism:
    def test_keys_are_line_independent(self):
        a = Violation("p", "r", "f.py::C.m", "d", source="f.py:10")
        b = Violation("p", "r", "f.py::C.m", "d", source="f.py:999")
        assert a.key == b.key == violation_key("p", "r", "f.py::C.m", "d")

    def test_waiver_and_staleness(self):
        rep = AnalysisReport(violations=[
            Violation("p", "r", "w", "real")])
        rep.apply_baseline({violation_key("p", "r", "w", "real"): "ok",
                            violation_key("p", "r", "w", "gone"): "stale"})
        assert rep.ok and rep.violations[0].waived
        assert rep.unused_baseline == [violation_key("p", "r", "w", "gone")]

    def test_unwaived_violation_fails(self):
        rep = AnalysisReport(violations=[Violation("p", "r", "w", "d")])
        rep.apply_baseline({})
        assert not rep.ok and rep.active


class TestRealStackIsClean:
    def test_analysis_passes_on_current_mode(self, tmp_path):
        """ISSUE 7 acceptance: ``python -m repro.analysis`` exits 0 on main
        under the committed baseline, with no stale waivers — exercised
        in-process through the CLI entry point; CI fans this out over
        dense/gather/fused via REPRO_KERNEL_MODE."""
        from repro.analysis.__main__ import main
        out = tmp_path / "report.json"
        assert main(["--out", str(out)]) == 0
        rep = json.loads(out.read_text())
        assert rep["ok"] and not rep["unused_baseline"]
        assert set(rep["passes_run"]) == {
            "no-dense-far-view", "f32-accumulation", "no-host-sync",
            "vmem-budget", "no-collectives", "pool-ownership"}
        from repro.analysis.targets import kernel_mode
        want = 8 + (1 if kernel_mode() == "fused" and jax.device_count() > 1
                    else 0)   # + the mesh-sharded decode step (mesh-4dev CI)
        assert len(rep["targets_run"]) == want
        assert "chunk_prefill" in rep["targets_run"], \
            "the chunked admission-prefill step must be under analysis"

    def test_planted_target_fails_through_runner(self):
        """End to end: a broken target injected into the runner flips the
        exit contract (the framework is not green by construction)."""
        def bad(pt, sop):
            return (pt[:, :, None] == sop[None, None, :]).sum()
        t = _target(bad, (jnp.zeros((B, N_PAGES), jnp.int32),
                          jnp.zeros((C,), jnp.int32)),
                    forbidden_shapes=(ForbiddenShape(
                        (B, N_PAGES, C), "b-npages-c", "planted"),))
        rep = run_analysis(mode="dense", targets=[t], with_ownership=False,
                           baseline={})
        assert not rep.ok
        assert rep.active[0].pass_name == "no-dense-far-view"
