"""Substrate tests: data pipeline, optimizer, compression, checkpointing,
fault tolerance, elastic resharding."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import InputShape
from repro.configs.registry import ARCHS
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim import adamw, compression
from repro.runtime import elastic
from repro.runtime.fault_tolerance import (Heartbeat, RetryPolicy,
                                           StragglerDetector, run_supervised)

SHAPE = InputShape("t", seq_len=32, global_batch=4, kind="train")


class TestDataPipeline:
    def test_deterministic_across_instances(self):
        arch = ARCHS["qwen3-1.7b"].reduced()
        a = SyntheticLM(arch, SHAPE).batch(7)
        b = SyntheticLM(arch, SHAPE).batch(7)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_shards_partition_global_batch(self):
        arch = ARCHS["qwen3-1.7b"].reduced()
        full = SyntheticLM(arch, SHAPE, rank=0, world=1).batch(3)
        r0 = SyntheticLM(arch, SHAPE, rank=0, world=2).batch(3)
        r1 = SyntheticLM(arch, SHAPE, rank=1, world=2).batch(3)
        np.testing.assert_array_equal(
            np.concatenate([r0["tokens"], r1["tokens"]]), full["tokens"])

    def test_labels_shift_tokens(self):
        arch = ARCHS["qwen3-1.7b"].reduced()
        b = SyntheticLM(arch, SHAPE).batch(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_modality_batches(self):
        for name in ("qwen2-vl-2b", "musicgen-medium"):
            arch = ARCHS[name].reduced()
            b = SyntheticLM(arch, SHAPE).batch(0)
            if name == "qwen2-vl-2b":
                assert "patch_embeds" in b and "positions" in b
            else:
                assert "frame_embeds" in b and b["labels"].ndim == 3


class TestAdamW:
    def _params(self):
        k = jax.random.key(0)
        return {"w": jax.random.normal(k, (64, 32)),
                "b": jnp.zeros((32,))}

    @pytest.mark.parametrize("moment_dtype", ["f32", "int8"])
    def test_converges_on_quadratic(self, moment_dtype):
        cfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0,
                                moment_dtype=moment_dtype)
        params = self._params()
        target = jax.tree.map(lambda p: jnp.ones_like(p), params)
        state = adamw.init(params, cfg)

        def loss(p):
            return sum(jnp.sum((a - t) ** 2) for a, t in
                       zip(jax.tree.leaves(p), jax.tree.leaves(target)))

        l0 = float(loss(params))
        for _ in range(60):
            grads = jax.grad(loss)(params)
            params, state, _ = adamw.update(params, grads, state, cfg, cfg.lr)
        assert float(loss(params)) < 0.05 * l0

    def test_int8_state_is_smaller(self):
        params = {"w": jnp.zeros((1024, 256))}
        s8 = adamw.init(params, adamw.AdamWConfig(moment_dtype="int8"))
        s32 = adamw.init(params, adamw.AdamWConfig(moment_dtype="f32"))
        bytes8 = sum(np.asarray(x).nbytes for x in jax.tree.leaves(s8))
        bytes32 = sum(np.asarray(x).nbytes for x in jax.tree.leaves(s32))
        assert bytes8 < 0.35 * bytes32

    def test_grad_clipping(self):
        cfg = adamw.AdamWConfig(grad_clip=1.0)
        params = self._params()
        state = adamw.init(params, cfg)
        grads = jax.tree.map(lambda p: 1e6 * jnp.ones_like(p), params)
        new_params, _, m = adamw.update(params, grads, state, cfg, 1e-3)
        assert float(m["grad_norm"]) > 1e5
        delta = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                    zip(jax.tree.leaves(new_params), jax.tree.leaves(params)))
        assert delta < 0.1  # clipped update is bounded


class TestGradCompression:
    def test_error_feedback_unbiased_over_steps(self):
        """Accumulated compressed updates converge to accumulated truth."""
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        g = jax.random.normal(jax.random.key(0), (256,))
        residual = {"g": jnp.zeros((256,))}
        total_c = jnp.zeros((256,))
        total_t = jnp.zeros((256,))
        from functools import partial
        from jax.sharding import PartitionSpec as P

        @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def step(gi, r):
            out, new_r = compression.compress_psum(
                {"g": gi}, {"g": r}, ("data",))
            return out["g"], new_r["g"]

        r = residual["g"]
        for i in range(20):
            gi = g * (1.0 + 0.01 * i)
            out, r = step(gi, r)
            total_c += out
            total_t += gi
        err = float(jnp.linalg.norm(total_c - total_t)
                    / jnp.linalg.norm(total_t))
        assert err < 0.02, err

    def test_wire_format_is_int8(self):
        """The all-reduced payload must be 8-bit (4x compression)."""
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        from functools import partial
        from jax.sharding import PartitionSpec as P

        @partial(jax.shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(),
                 check_vma=False)
        def f(g):
            out, _ = compression.compress_psum(
                {"g": g}, {"g": jnp.zeros_like(g)}, ("data",))
            return out["g"]

        txt = jax.jit(f).lower(jnp.zeros((1024,))).as_text()
        assert "s8" in txt or "i8" in txt


class TestCheckpointManager:
    def _tree(self, x=1.0):
        return {"a": jnp.full((8, 8), x), "b": {"c": jnp.arange(5)}}

    def test_roundtrip(self, tmp_path):
        m = CheckpointManager(tmp_path)
        m.save(10, self._tree(2.0), extra={"data_step": 10})
        tree, extra = m.restore(self._tree())
        np.testing.assert_allclose(tree["a"], 2.0)
        assert extra["data_step"] == 10

    def test_async_save(self, tmp_path):
        m = CheckpointManager(tmp_path)
        m.save(1, self._tree(3.0), blocking=False)
        m.wait()
        tree, _ = m.restore(self._tree())
        np.testing.assert_allclose(tree["a"], 3.0)

    def test_atomic_no_partial_checkpoint(self, tmp_path):
        m = CheckpointManager(tmp_path)
        m.save(1, self._tree())
        assert not list(tmp_path.glob("*.tmp"))

    def test_retention(self, tmp_path):
        m = CheckpointManager(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            m.save(s, self._tree(float(s)))
        assert m.all_steps() == [3, 4]

    def test_corruption_detected_and_fallback(self, tmp_path):
        m = CheckpointManager(tmp_path, keep=5)
        m.save(1, self._tree(1.0))
        m.save(2, self._tree(2.0))
        # corrupt the newest checkpoint
        victim = next((tmp_path / "step_0000000002").rglob("leaf_00000.npy"))
        arr = np.load(victim)
        np.save(victim, arr + 1)
        with pytest.raises(IOError):
            m.restore(self._tree(), 2)
        tree, _ = m.restore_with_fallback(self._tree())
        np.testing.assert_allclose(tree["a"], 1.0)  # fell back to step 1


class TestFaultTolerance:
    def test_straggler_detection(self):
        det = StragglerDetector(strikes_to_flag=3)
        flagged = []
        for step in range(10):
            times = {f"host{i}": 1.0 + 0.01 * i for i in range(16)}
            times["host7"] = 5.0  # persistent straggler
            flagged = det.observe_step(times)
        assert flagged == ["host7"]

    def test_transient_blip_not_flagged(self):
        det = StragglerDetector(strikes_to_flag=3)
        for step in range(10):
            times = {f"host{i}": 1.0 for i in range(16)}
            if step == 4:
                times["host3"] = 9.0  # single blip
            assert det.observe_step(times) == []

    def test_run_supervised_restarts(self):
        calls = {"n": 0}

        def loop():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("simulated node failure")
            return 100, "state"

        step, state = run_supervised(loop, None, RetryPolicy(backoff_s=0.0))
        assert step == 100 and calls["n"] == 3

    def test_heartbeat(self):
        hb = Heartbeat(timeout_s=10.0)
        hb.beat("a", now=0.0)
        hb.beat("b", now=8.0)
        assert hb.dead_hosts(now=11.0) == ["a"]


class TestElastic:
    def test_plan_shrinks_dp_preserves_tp(self):
        plan = elastic.plan_mesh(n_devices=192, model_parallel=16,
                                 target_dp=16)
        assert plan.mesh_shape == (12, 16) or plan.mesh_shape[1] == 16
        assert plan.dp_size * plan.grad_accum >= 16

    def test_plan_exact_fit(self):
        plan = elastic.plan_mesh(256, 16, 16)
        assert plan.mesh_shape == (16, 16)
        assert plan.grad_accum == 1

    def test_plan_rejects_too_few(self):
        with pytest.raises(ValueError):
            elastic.plan_mesh(8, 16, 16)

    def test_reshard_on_local_devices(self):
        arch = ARCHS["qwen3-1.7b"].reduced()
        from repro.models import transformer
        params = transformer.init_params(jax.random.key(0), arch)
        plan = elastic.plan_mesh(len(jax.devices()), 1,
                                 target_dp=len(jax.devices()))
        mesh = elastic.build_mesh(plan)
        placed = elastic.reshard(params, arch, mesh)
        for a, b in zip(jax.tree.leaves(placed), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
