"""GPipe-style pipeline parallelism over a mesh axis via shard_map + ppermute.

Each device on the pipeline axis holds one contiguous stage of layers.
Microbatches stream through: at tick t, stage s computes microbatch t−s and
passes its activation to stage s+1 with ``collective_permute``; total ticks =
n_micro + n_stages − 1 (the classic bubble). This is the cross-pod option
for models whose layer stacks exceed one pod's HBM; the default multi-pod
config uses the pod axis as DP instead (launch/mesh.py).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_micro, mesh: Mesh, axis: str):
    """Run a pipelined stack.

    stage_fn(params_for_one_stage, x) → x  (same shape)
    stage_params: pytree with leading dim n_stages (sharded over ``axis``)
    x_micro: (n_micro, mb, ...) microbatched inputs (replicated)
    Returns (n_micro, mb, ...) outputs of the last stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1

    def local(params_local, x_all):
        # params_local: leading dim 1 (this stage); x_all replicated
        p_stage = jax.tree.map(lambda a: a[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        mb_shape = x_all.shape[1:]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t; others take the permuted input
            feed = jnp.where(t < n_micro, t, 0)
            injected = x_all[feed]
            state = jnp.where(stage_id == 0, injected, state)
            out = stage_fn(p_stage, state)
            # last stage records its finished microbatch (t - (n_stages-1))
            done_idx = t - (n_stages - 1)
            do_write = (stage_id == n_stages - 1) & (done_idx >= 0)
            outputs = jax.lax.cond(
                do_write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(done_idx, 0), 0),
                lambda o: o,
                outputs)
            # shift downstream: stage s → s+1
            state = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (state, outputs), None

        init = (jnp.zeros(mb_shape, x_all.dtype),
                jnp.zeros((n_micro,) + mb_shape, x_all.dtype))
        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))
        # every stage holds an `outputs` buffer; only the last stage's is
        # real — zero the rest and psum to replicate it everywhere
        outputs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outputs, 0.0), axis)
        return outputs

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params,
                               is_leaf=lambda x: hasattr(x, "shape")), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_micro)
