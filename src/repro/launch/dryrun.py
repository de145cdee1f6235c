import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init).  This module is the proof that the distribution config
is coherent: for the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh,
``jax.jit(step).lower(...).compile()`` must succeed for every cell, and the
compiled artifact's memory/cost analysis feeds docs/experiments.md §Dry-run and
§Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod
  python -m repro.launch.dryrun --list
"""

import argparse   # noqa: E402
import json       # noqa: E402
import sys        # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax                # noqa: E402
import jax.numpy as jnp   # noqa: E402

from repro.configs.base import shape_applicable          # noqa: E402
from repro.configs.registry import ARCHS, SHAPES         # noqa: E402
from repro.launch import hlo_analysis, serve, train      # noqa: E402
from repro.launch.mesh import make_production_mesh       # noqa: E402
from repro.models import model_zoo, transformer          # noqa: E402
from repro.optim import adamw                            # noqa: E402
from repro.sharding import ctx                           # noqa: E402
from repro.sharding import specs as sh                   # noqa: E402

DEFAULT_OUT = Path("artifacts/dryrun")


def _sds_with(tree_shapes, shardings):
    return jax.tree.map(
        lambda s, sd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sd),
        tree_shapes, shardings)


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               fsdp: bool = True, remat: str = "full",
               moment_dtype: str = "f32", seq_shard: bool = False,
               group_size: int = 1024, pad_heads: int = 0,
               grad_dtype: str = "f32", shard_logits: bool = False,
               sparse_kv_pages: int = 0, moe_impl: str = "einsum",
               moe_group: int = 1024, serve_dtype: str = "f32",
               params_dtype: str = "f32"):
    """Lower + compile one cell; returns the artifact dict.

    Perf-iteration knobs (docs/experiments.md §Perf):
      pad_heads: pad Q heads to N so they divide the model axis (TP for
        awkward head counts; dummy heads are function-preserving).
      grad_dtype: 'bf16' reduces gradients in bf16 (half the DP wire bytes).
      shard_logits: keep output logits vocab-sharded instead of replicated.
      sparse_kv_pages: decode attends near-tier pages + recent window only
        (the TL-DRAM sparse serving mode; >0 enables with that many pages).
    """
    import dataclasses as _dc

    from repro.models import moe as moe_lib

    arch = ARCHS[arch_name]
    shape = SHAPES[shape_name]
    if pad_heads:
        arch = _dc.replace(arch, n_heads=pad_heads,
                           head_dim=arch.resolved_head_dim)
    moe_lib.DEFAULT_IMPL = moe_impl
    moe_lib.DEFAULT_GROUP_SIZE = moe_group
    ok, why = shape_applicable(arch, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    cfg = train.TrainConfig(
        remat=remat, grad_dtype=grad_dtype,
        adamw=adamw.AdamWConfig(moment_dtype=moment_dtype))

    p_dtype = jnp.float32
    if serve_dtype == "bf16" and shape.kind != "train":
        p_dtype = jnp.bfloat16
    if params_dtype == "bf16":
        # bf16 parameters end-to-end (f32 lives only in optimizer moments):
        # FSDP all-gathers and gradient reductions move bf16 on the wire.
        p_dtype = jnp.bfloat16
    param_shapes = jax.eval_shape(
        lambda: transformer.init_params(jax.random.key(0), arch,
                                        dtype=p_dtype))
    pspecs = sh.param_specs(param_shapes, arch, mesh, fsdp=fsdp)
    pshard = sh.to_named(pspecs, mesh)
    params_sds = _sds_with(param_shapes, pshard)

    batch_shapes = model_zoo.input_specs(arch, shape)
    bspecs = sh.batch_specs(batch_shapes, arch, shape, mesh,
                            seq_shard=seq_shard)
    bshard = sh.to_named(bspecs, mesh)
    batch_sds = _sds_with(batch_shapes, bshard)

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(
            lambda: adamw.init(param_shapes, cfg.adamw))
        ospecs = sh.moment_specs(pspecs, opt_shapes, mesh, fsdp=fsdp)
        oshard = sh.to_named(ospecs, mesh)
        opt_sds = _sds_with(opt_shapes, oshard)
        step_fn = train.make_train_step(arch, cfg)
        with mesh, ctx.activation_sharding(mesh, seq_shard=seq_shard):
            lowered = jax.jit(
                step_fn,
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            ).lower(params_sds, opt_sds, batch_sds)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        step_fn = serve.make_prefill_step(arch, max_len=shape.seq_len)
        cache_shapes = jax.eval_shape(
            lambda: transformer.init_cache(arch, shape.global_batch,
                                           shape.seq_len))
        cspecs = sh.cache_specs(cache_shapes, arch, mesh)
        cshard = sh.to_named(cspecs, mesh)
        with mesh, ctx.activation_sharding(mesh, seq_shard=seq_shard):
            lowered = jax.jit(
                step_fn, out_shardings=(None, cshard),
            ).lower(params_sds, batch_sds)
            compiled = lowered.compile()
    else:  # decode
        if sparse_kv_pages and arch.n_heads and not arch.sliding_window:
            step_fn = serve.make_sparse_tiered_decode_step(
                arch, near_pages=sparse_kv_pages)
            extras = jax.eval_shape(
                lambda: serve.sparse_cache_extras(arch, shape.global_batch,
                                                  shape.seq_len,
                                                  sparse_kv_pages, 128))
        else:
            step_fn = serve.make_decode_step(arch)
            extras = {}
        cache_shapes = jax.eval_shape(
            lambda: transformer.init_cache(arch, shape.global_batch,
                                           shape.seq_len))
        cache_shapes = {**cache_shapes, **extras}
        cspecs = sh.cache_specs(cache_shapes, arch, mesh)
        cshard = sh.to_named(cspecs, mesh)
        cache_sds = _sds_with(cache_shapes, cshard)
        with mesh, ctx.activation_sharding(mesh, seq_shard=seq_shard):
            lowered = jax.jit(
                step_fn, out_shardings=(None, cshard), donate_argnums=(1,),
            ).lower(params_sds, cache_sds, batch_sds)
            compiled = lowered.compile()

    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = hlo_analysis.analyze_module(compiled.as_text())

    art = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": mesh.devices.size,
        "status": "ok",
        "compile_seconds": round(compile_s, 1),
        "config": {"fsdp": fsdp, "remat": remat, "moe_impl": moe_impl,
                   "moment_dtype": moment_dtype, "seq_shard": seq_shard,
                   "pad_heads": pad_heads, "grad_dtype": grad_dtype,
                   "sparse_kv_pages": sparse_kv_pages,
                   "serve_dtype": serve_dtype,
                   "params_dtype": params_dtype},
        "params": arch.param_count(),
        "active_params": arch.active_param_count(),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        # xla_cost: per-device, but while-loop bodies counted ONCE (see
        # hlo_analysis docstring) — kept for reference only.
        "xla_cost": {"flops": cost.get("flops", 0.0),
                     "bytes_accessed": cost.get("bytes accessed", 0.0)},
        # hlo: loop-aware per-device totals used by the roofline.
        "hlo": hlo.as_dict(),
    }
    return art


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--moment-dtype", default="f32")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--pad-heads", type=int, default=0)
    ap.add_argument("--grad-dtype", default="f32")
    ap.add_argument("--sparse-kv-pages", type=int, default=0)
    ap.add_argument("--moe-impl", default="einsum")
    ap.add_argument("--moe-group", type=int, default=1024)
    ap.add_argument("--serve-dtype", default="f32")
    ap.add_argument("--params-dtype", default="f32")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    if args.list:
        for a in archs:
            for s in shapes:
                ok, why = shape_applicable(ARCHS[a], SHAPES[s])
                print(f"{a:26s} {s:12s} {'run' if ok else 'SKIP: ' + why}")
        return 0

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for a in archs:
        for s in shapes:
            for mp in meshes:
                tag = f"{a}__{s}__{'multi' if mp else 'single'}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = out_dir / f"{tag}.json"
                try:
                    art = lower_cell(a, s, mp, fsdp=bool(args.fsdp),
                                     remat=args.remat,
                                     moment_dtype=args.moment_dtype,
                                     seq_shard=args.seq_shard,
                                     pad_heads=args.pad_heads,
                                     grad_dtype=args.grad_dtype,
                                     sparse_kv_pages=args.sparse_kv_pages,
                                     moe_impl=args.moe_impl,
                                     moe_group=args.moe_group,
                                     serve_dtype=args.serve_dtype,
                                     params_dtype=args.params_dtype)
                except Exception as e:  # noqa: BLE001 — report, keep going
                    art = {"arch": a, "shape": s,
                           "mesh": "multi" if mp else "single",
                           "status": "failed", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    failures += 1
                path.write_text(json.dumps(art, indent=1))
                status = art["status"]
                extra = (f"compile={art.get('compile_seconds')}s"
                         if status == "ok" else art.get("reason",
                                                        art.get("error", "")))
                print(f"[dryrun] {tag}: {status} {extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
