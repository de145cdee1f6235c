"""GPipe-style pipeline parallelism over the 'pod' mesh axis.

The default multi-pod layout treats 'pod' as outer data parallelism; this
module provides the alternative: layers split into one stage per pod,
microbatches streamed through stages with ``shard_map`` + ``ppermute``
(jax-native collective-permute — the cross-pod DCN/ICI hop), compute
overlapping communication in the classic fill/steady/drain schedule.

Design notes:
  * stage function must be shape-preserving on (B_mb, S, D) activations —
    true for every decoder block here;
  * stage parameters are stacked on a leading stage axis sharded over 'pod'
    (each pod holds only its stage's layers);
  * the schedule runs M + P - 1 ticks for M microbatches and P stages; the
    bubble fraction (P-1)/(M+P-1) is reported by ``bubble_fraction``;
  * within a stage, all other axes ('data', 'model') keep their usual roles,
    so PP composes with DP/TP/FSDP.

The multi-pod dry-run lowers a pipelined train step for qwen3
(`launch/dryrun.py --pipeline`), proving the pod axis shards under this
schedule too; numerics are tested on a 1-stage mesh (identity schedule) in
tests and exactness across stages is asserted by construction (each tick
applies the same block function).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def gpipe_apply(block_fn, layer_params, x, mesh: Mesh,
                n_microbatches: int, axis: str = "pod"):
    """Run ``block_fn(local_layer_params, x_mb) -> x_mb`` through P stages.

    layer_params: pytree with leading layer dim L, *already sharded over
    ``axis`` on that dim at the jit boundary* (see ``pp_param_specs``) —
    shard_map then hands each pod its own L/P layer slice with no resharding.
    x: (B, S, D) activations (B divisible by n_microbatches).
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_microbatches == 0
    mb = B // n_microbatches

    # Fully-manual shard_map: stages over `axis`, batch over the data axes,
    # weights replicated across 'model' inside the stage.  (Mixed
    # manual/auto shard_map — which would let GSPMD run TP inside each
    # stage — trips an XLA CPU SPMD-partitioner check-failure on this
    # container [b/433785288]; on TPU backends / Shardy the mixed mode is
    # the intended composition.  Embedding and LM head remain vocab-sharded
    # outside the pipelined region either way.)
    pspec = jax.tree.map(lambda _: P(axis), layer_params)
    data_axes = tuple(a for a in mesh.axis_names if a not in (axis, "model"))
    xspec = P(data_axes if data_axes else None)   # batch over data, repl. over pod
    manual = set(mesh.axis_names)

    @functools.partial(jax.shard_map, mesh=mesh, axis_names=manual,
                       in_specs=(pspec, xspec), out_specs=xspec,
                       check_vma=False)
    def run(params_local, x_local):
        stage = jax.lax.axis_index(axis)
        n_ticks = n_microbatches + n_stages - 1
        mb_local = x_local.shape[0] // n_microbatches
        assert mb_local >= 1, (x_local.shape, n_microbatches)
        x_mb = x_local.reshape(n_microbatches, mb_local, *x_local.shape[1:])
        buf = jnp.zeros_like(x_mb[0])
        out = jnp.zeros_like(x_mb)

        def tick(t, carry):
            buf, out = carry
            # stage 0 ingests microbatch t (when in range)
            feed = x_mb[jnp.clip(t, 0, n_microbatches - 1)]
            inp = jnp.where(stage == 0, feed, buf)
            active = (t >= stage) & (t - stage < n_microbatches)
            y = block_fn(params_local, inp)
            y = jnp.where(active, y, buf)
            # last stage emits microbatch (t - stage)
            idx = jnp.clip(t - stage, 0, n_microbatches - 1)
            emit = active & (stage == n_stages - 1)
            out = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, y, idx, 0),
                lambda o: o, out)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, out

        _, out = jax.lax.fori_loop(0, n_ticks, tick, (buf, out))
        # Only the last stage holds real outputs; broadcast to every stage
        # (masked psum) so the result is replicated over `axis`.
        if n_stages > 1:
            out = jax.lax.psum(
                jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
                axis)
        return out.reshape(x_local.shape)

    return run(layer_params, x)


def pp_param_specs(param_shapes, arch, mesh: Mesh, axis: str = "pod",
                   fsdp: bool = True):
    """Standard param specs, with every layer-stacked leaf's leading layer
    dim additionally sharded over the pipeline axis (the stage split)."""
    from repro.sharding import specs as sh

    # 'pod' is the pipeline axis; layer weights enter the fully-manual
    # pipelined region replicated over 'model'/'data' (see gpipe_apply).
    base = sh.param_specs(param_shapes, arch, mesh, fsdp=False)

    def add_stage_dim(path, spec, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        if names and names[0] == "layers":
            return P(axis, *([None] * (len(leaf.shape) - 1)))
        return spec

    return jax.tree_util.tree_map_with_path(add_stage_dim, base, param_shapes)


def split_stages(stacked_layer_params, n_stages: int):
    """(L, ...) stacked layer params -> (P, L/P, ...) stage-stacked."""
    def f(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree.map(f, stacked_layer_params)


def make_pp_loss_fn(arch, mesh: Mesh, n_microbatches: int = 8,
                    axis: str = "pod"):
    """A pipelined forward+loss for decoder archs: layers split into one
    stage per pod, each stage scanning its layer slice.  Composes with the
    usual DP/TP shardings on the other axes.  Used by the dry-run to prove
    the pod axis pipelines (`--pipeline`)."""
    from repro.models import transformer  # local import avoids cycles

    n_stages = mesh.shape[axis]
    assert arch.n_layers % n_stages == 0, (arch.n_layers, n_stages)

    def block_fn(stage_params, x):
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                     (x.shape[0], S))

        def body(h, layer_params):
            h, *_ = transformer._block_train(layer_params, h, positions,
                                             arch)
            return h, None

        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    def loss_fn(params, batch):
        x = jnp.take(params["embed"], batch["tokens"], axis=0
                     ).astype(jnp.bfloat16)
        cparams = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 and a.ndim > 1 else a,
            params["layers"])
        x = gpipe_apply(block_fn, cparams, x, mesh, n_microbatches, axis)
        from repro.models.layers import rms_norm
        x = rms_norm(x, params["final_norm"].astype(jnp.bfloat16))
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params.get("lm_head", params["embed"].T))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None],
                                   axis=-1)[..., 0]
        return nll.mean()

    return loss_fn
