"""Pallas kernel: page-granular KV gather from the shared far pool.

The paged far tier (docs/design.md §2d) keeps one refcounted pool of KV
pages; each slot's far view is its page table resolved against the pool.
XLA lowers that resolution to a row gather — fine, but grain-agnostic.  This
kernel exploits the page structure: the unit of transfer is a whole
(page, Hkv*hd) panel, so each grid step issues ONE dynamic VMEM load per
page instead of per-row gathers — the TL-DRAM observation that the far
segment's cost is per-activation, not per-bit, applied to the gather path.

Grid: (B, n_pages), the page-id table in SMEM via scalar prefetch.  VMEM
per step: the full pool (one resident copy — its block index never changes,
so it is single-buffered) plus one double-buffered output page — so the
kernel REFUSES pools larger than ``vmem_budget_bytes`` (default 64 MiB, half
a v5e core's 128 MiB of VMEM) with a clear ``ValueError`` instead of
letting the compiler OOM or silently spill; the scoped VMEM limit it asks
the compiler for is sized from the pool.  It is a small-pool path only: no
serving caller depends on it at deployment pool sizes.  The fused walk
kernel (`kernels.paged_attention`) is the production-shaped alternative: it
keeps the pool in HBM/ANY and DMAs one page per live, non-promoted page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_VMEM_BUDGET = 64 * 2 ** 20     # bytes of VMEM the pool may pin
_VMEM_SLACK = 4 * 2 ** 20              # compiler-internal scratch headroom


def _paged_gather_kernel(ids_ref, pool_ref, o_ref, *, n_pages: int):
    pid = ids_ref[pl.program_id(0) * n_pages + pl.program_id(1)]
    panel = pool_ref[jnp.maximum(pid, 0)]                       # (page, D)
    o_ref[0] = jnp.where(pid >= 0, panel, 0).astype(o_ref.dtype)


def paged_gather(pool: jax.Array, page_ids: jax.Array,
                 interpret: bool = False,
                 vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET,
                 mesh=None) -> jax.Array:
    """pool: (P, page, Hkv, hd); page_ids: (B, n_pages) int32 (< 0 => zeros).

    Returns (B, n_pages*page, Hkv, hd): each row b is the contiguous
    materialization of b's page table against the pool.

    Raises ValueError when the pool would pin more than
    ``vmem_budget_bytes`` of VMEM per grid step.

    With a ``mesh`` whose 'model' axis divides Hkv the pool is
    KV-HEAD-SHARDED: each device gathers its head slice (a 1/m-size pool
    shard also means the VMEM budget is priced per SHARD) and a tiled
    ``all_gather`` over 'model' re-assembles the replicated view — the
    gather is a pure byte move, so the result is exactly the
    single-device materialization."""
    from repro.sharding.specs import kv_shard_count
    if mesh is not None and kv_shard_count(mesh, pool.shape[-2]) > 1:
        from jax.sharding import PartitionSpec as P_

        def local_gather(pk, ids):
            out = paged_gather(pk, ids, interpret=interpret,
                               vmem_budget_bytes=vmem_budget_bytes)
            return jax.lax.all_gather(out, "model", axis=2, tiled=True)

        return jax.shard_map(
            local_gather, mesh=mesh,
            in_specs=(P_(None, None, "model"), P_()),
            out_specs=P_(), check_vma=False)(pool, page_ids)
    P, page, Hkv, hd = pool.shape
    B, n_pages = page_ids.shape
    D = Hkv * hd
    pool_bytes = P * page * D * pool.dtype.itemsize
    if pool_bytes > vmem_budget_bytes:
        raise ValueError(
            f"paged_gather maps the whole pool into VMEM per grid step: "
            f"pool is {pool_bytes} bytes ({P} pages x {page} x {D} x "
            f"{pool.dtype.itemsize}B) > budget {vmem_budget_bytes}. "
            f"Use the fused walk kernel (kernels.paged_attention, "
            f"TieredKVConfig.fused_kernel) for pools this large, or raise "
            f"vmem_budget_bytes explicitly.")
    out_bytes = 2 * page * D * pool.dtype.itemsize   # double-buffered block
    out = pl.pallas_call(
        functools.partial(_paged_gather_kernel, n_pages=n_pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_pages),
            in_specs=[pl.BlockSpec((P, page, D), lambda b, j, *_: (0, 0, 0),
                                   pipeline_mode=pl.Buffered(1))],
            out_specs=pl.BlockSpec((1, page, D),
                                   lambda b, j, *_: (b, j, 0))),
        out_shape=jax.ShapeDtypeStruct((B, n_pages * page, D), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=pool_bytes + out_bytes + _VMEM_SLACK),
        name="paged_gather",
        interpret=interpret,
    )(jnp.asarray(page_ids, jnp.int32).reshape(-1), pool.reshape(P, page, D))
    return out.reshape(B, n_pages * page, Hkv, hd)
