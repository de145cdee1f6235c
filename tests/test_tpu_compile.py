"""AOT compiles for a described TPU v5e: what interpret mode cannot see.

The Pallas kernels of the served read path, and one full 28-layer decode
step of Qwen3-1.7B, are lowered and compiled by the TPU compiler for a
``v5e:2x2`` topology that is described, not attached — tiling refusals,
VMEM overruns and programs that do not fit the chip's 16 GB of HBM fail
here, at no chip time.  Nothing runs: these tests say nothing about results
or speed.  The topology is described inside a fixture, so collecting this
file never loads the TPU library; the tests skip where it cannot be
described.

Code that asks ``jax.default_backend()`` still sees this sandbox's CPU and
would run the kernels in interpret mode (``repro.kernels.interpret_mode``),
which lowers them to plain HLO; the whole-step compiles below steer that
one question to "tpu" so the compiled program holds the real kernel.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.configs.registry import ARCHS
from repro.core import tiered_kv as tkv
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_gather import paged_gather
from repro.kernels.paged_masses import paged_masses
from repro.launch.serve import make_paged_tiered_decode_step
from repro.models import transformer

# Qwen3-1.7B's published attention widths; one slot pool of the serving
# deployment the chip smoke runs (8 slots x 4096 tokens, 16-token pages)
B, H, HKV, HD, PAGE, NEAR = 8, 16, 8, 128, 16, 4
MAX_LEN = 4096
N_PAGES = MAX_LEN // PAGE
HBM_BYTES = 16e9                          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def shape(one_chip, no_persistent_cache):
    def make(dims, dtype=jnp.bfloat16, sharding=one_chip):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)
    return make


@pytest.fixture
def tpu_backend(monkeypatch):
    """Kernels inside a traced step compile for the chip, not interpret."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _collectives(hlo: str) -> set[str]:
    return set(re.findall(r"\b(all-gather|all-reduce|reduce-scatter|"
                          r"collective-permute|all-to-all)(?:-start)?\(",
                          hlo))


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _walk_args(shape, W=N_PAGES):
    i32 = jnp.int32
    return (shape((B, W), i32), shape((B, W), i32), shape((B,), i32))


@pytest.mark.parametrize("stacked,heads,kv_heads", [
    pytest.param(False, H, HKV, id="one-layer-pool"),
    pytest.param(True, H, HKV, id="stacked-pools"),
    pytest.param(True, 32, 4, id="yi-9b-stacked-pools"),     # GQA group 8
])
def test_paged_attention_compiles(shape, stacked, heads, kv_heads):
    """The fused walk, on one layer's pool and on the decode step's
    stacked (L, P, page, Hkv, hd) pools read at a traced layer, at the
    deployment's W = 256 pages a slot: the two block buffers and their
    DMA semaphores pass the chip's compiler at Qwen3-1.7B's and Yi-9B's
    widths."""
    lead = (2,) if stacked else ()
    pool = shape(lead + (512, PAGE, kv_heads, HD))
    near = shape((NEAR * PAGE, kv_heads, HD))
    args = (shape((B, heads, HD)), pool, pool, near, near,
            *_walk_args(shape), shape((B, NEAR), jnp.int32))
    if stacked:
        fn = lambda *a: paged_attention(*a[:-1], layer=a[-1])
        args += (shape((), jnp.int32),)
    else:
        fn = paged_attention
    compiled = _compile(fn, *args)
    assert compiled.memory_analysis() is not None


def test_paged_masses_compiles(shape):
    compiled = _compile(paged_masses, shape((B, H, HD), jnp.float32),
                        shape((512, PAGE, HKV, HD)), *_walk_args(shape))
    assert compiled.memory_analysis() is not None
    assert re.search(r"%paged_masses[.\d]* = .*custom_call_target="
                     r'"tpu_custom_call"', compiled.as_text())


def test_paged_gather_compiles_small_pool(shape):
    """The materializing gather maps the whole pool into VMEM: it compiles
    for a pool inside its budget, and is no deployment-size path."""
    compiled = _compile(paged_gather, shape((64, PAGE, HKV, HD)),
                        shape((B, 32), jnp.int32))
    assert compiled.memory_analysis() is not None
    assert re.search(r"%paged_gather[.\d]* = .*custom_call_target="
                     r'"tpu_custom_call"', compiled.as_text())


def _decode_step_args(shape, cfg, pool_sharding=None, near_sharding=None,
                      replicated=None):
    """Shapes of one full-width decode step's arguments: bf16 weights, the
    default pool ((n_slots + 4) * n_pages pages), walk metadata."""
    arch = ARCHS["qwen3-1.7b"]
    L = arch.n_layers
    P = (B + 4) * N_PAGES
    like = lambda tree, sh: jax.tree.map(
        lambda a: shape(a.shape, a.dtype, *([sh] if sh else [])), tree)
    params = like(jax.eval_shape(lambda: transformer.init_params(
        jax.random.key(0), arch, dtype=jnp.bfloat16)), replicated)
    tier = like(jax.eval_shape(
        lambda: tkv.init_tier_state(B, N_PAGES, P, NEAR)), replicated)
    pos = like(jax.ShapeDtypeStruct((B,), jnp.int32), replicated)
    meta = like(jax.eval_shape(
        lambda t, p: tkv.paged_step_metadata(t, p + 1, cfg, append_pos=p,
                                             pool_pages=P), tier, pos),
        replicated)
    pool = like(jax.ShapeDtypeStruct((L, P, PAGE, HKV, HD), jnp.bfloat16),
                pool_sharding)
    near = like(jax.ShapeDtypeStruct((L, NEAR * PAGE, HKV, HD),
                                     jnp.bfloat16), near_sharding)
    cache = {"pool_k": pool, "pool_v": pool, "near_k": near,
             "near_v": near, "pos": pos}
    tokens = like(jax.ShapeDtypeStruct((B, 1), jnp.int32), replicated)
    return arch, (params, cache, {"tokens": tokens}, meta), \
        2 * pool.size * 2


@pytest.mark.parametrize("fused", [False, True], ids=["dense", "fused"])
def test_full_width_decode_step_fits_one_chip(shape, tpu_backend, fused):
    """One 28-layer Qwen3-1.7B paged decode step with bf16 weights and the
    default pool, its KV cache donated as the serving engine donates it:
    weights + pool + temporaries fit 16 GB, the pool is updated in place
    (aliased, not copied), and the fused step holds the compiled walk
    kernel."""
    cfg = tkv.TieredKVConfig(page=PAGE, near_pages=NEAR, fused_kernel=fused)
    arch, args, pool_bytes = _decode_step_args(shape, cfg)
    compiled = _compile(make_paged_tiered_decode_step(arch, cfg), *args,
                        donate_argnums=(1,))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 10
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == fused
    # the walk keeps its name down to the compiled op, so a trace names it
    assert bool(re.search(r"%paged_walk[.\d]* = .*custom_call_target="
                          r'"tpu_custom_call"', hlo)) == fused


def test_head_sharded_decode_step_compiles_on_four_chips(topo, shape,
                                                         tpu_backend):
    """The KV-head-sharded fused step on a ('data' 1, 'model' 4) mesh of
    the described chips: each chip holds a quarter of the pool next to
    the replicated weights, and the only collectives are the walk's tiled
    all-gathers of its per-head stats."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    spec = lambda *dims: NamedSharding(mesh, PartitionSpec(*dims))
    cfg = tkv.TieredKVConfig(page=PAGE, near_pages=NEAR, fused_kernel=True,
                             mesh=mesh)
    arch, args, pool_bytes = _decode_step_args(
        shape, cfg, pool_sharding=spec(None, None, None, "model"),
        near_sharding=spec(None, None, "model"), replicated=spec())
    compiled = _compile(make_paged_tiered_decode_step(arch, cfg), *args,
                        donate_argnums=(1,))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes / 4
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _collectives(hlo) == {"all-gather"}
