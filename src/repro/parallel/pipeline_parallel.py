"""Pipeline parallelism: microbatched GPipe schedule over a ``pipe`` mesh
axis with collective_permute hops between stages.

The assigned production meshes are DP x TP, so PP is an opt-in third axis
(e.g. reshape the pod axis into stages).  The schedule below is the
standard fill/drain loop: with M microbatches and S stages it runs
M + S - 1 ticks; each tick every stage computes one microbatch and
ppermutes its activation to the next stage.  Autodiff through ppermute
gives the reverse hops for backward, so the same function trains.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "pipe",
):
    """Run ``stage_fn`` as an S-stage pipeline.

    stage_params: pytree with leading axis S (sharded over ``axis``).
    microbatches: [M, mb, ...] (replicated input; stage 0 consumes it).
    Returns [M, mb, ...] outputs (valid on every rank after the drain).
    """
    s_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    ticks = m + s_stages - 1

    def body(params_local, micro):
        stage = jax.lax.axis_index(axis)
        params_here = jax.tree_util.tree_map(lambda t: t[0], params_local)
        mb_shape = micro.shape[1:]
        out_buf = jnp.zeros((m,) + mb_shape, micro.dtype)
        recv = jnp.zeros(mb_shape, micro.dtype)

        def tick(t, carry):
            out_buf, recv = carry
            mb_idx = t - stage                      # microbatch at this stage
            valid = (mb_idx >= 0) & (mb_idx < m)
            x_in = jnp.where(
                stage == 0,
                micro[jnp.clip(mb_idx, 0, m - 1)],
                recv,
            )
            y = stage_fn(params_here, x_in)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage commits its output; others forward it on the ring
            out_buf = jax.lax.cond(
                valid & (stage == s_stages - 1),
                lambda ob: jax.lax.dynamic_update_index_in_dim(
                    ob, y, jnp.clip(mb_idx, 0, m - 1), 0
                ),
                lambda ob: ob,
                out_buf,
            )
            recv_next = jax.lax.ppermute(
                y, axis,
                [(i, i + 1) for i in range(s_stages - 1)],
            )
            return out_buf, recv_next

        out_buf, _ = jax.lax.fori_loop(0, ticks, tick, (out_buf, recv))
        # every rank returns the (psum-shared) final buffer
        return jax.lax.psum(out_buf, axis)

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        P(),
    )
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)


def sequential_reference(stage_fn, stage_params, microbatches):
    """Oracle: apply the stages sequentially (no pipeline)."""
    def one(mb):
        x = mb
        n = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        for i in range(n):
            params_i = jax.tree_util.tree_map(lambda t: t[i], stage_params)
            x = stage_fn(params_i, x)
        return x
    return jax.vmap(one)(microbatches)
