"""MapReduce-on-mesh engine: map shards + collective shuffle + replicated reduce.

The paper's job anatomy (map over input chunks, shuffle intermediate
key-values, reduce) maps onto a JAX device mesh as (DESIGN.md §2):

  map task   -> one `shard_map` shard along the ``data`` axis
  shuffle    -> the collective that moves map outputs (all_gather / psum /
                ring top-k merge); its byte count is the paper's shuffle cost
  reduce     -> a replicated combine over the gathered outputs

The engine is deliberately thin: apps give it a ``map_fn`` (typically the
two-stage refine skeleton) and a ``CombineSpec``.  It also *meters* shuffle
bytes so the fig.5 benchmark can report the paper's percentage-shuffle-cost
metric from the same code path that runs on the pod mesh.

Spans (``repro.obs.trace``): ``mapreduce`` holds ``map.shard`` (the map's
dispatch), ``map.meter`` (the shuffle metering) and ``reduce`` (the
combine).  They time host work only and never block: the device trace,
on the same clock, says when the enqueued work ran.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.trace import current_tracer


def _tree_bytes(tree: Any) -> int:
    """Static byte size of a pytree of (Shape)DtypeStructs or arrays."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        total += math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
    return total


@dataclasses.dataclass(frozen=True)
class CombineSpec:
    """How map outputs become the job result.

    mode:
      * "all_gather" — gather per-shard outputs along a new leading axis and
        hand them to ``reduce_fn`` (general; shuffle bytes = sum of outputs).
      * "psum"       — elementwise sum across shards (cheap reductions, e.g.
        CF partial numerators); shuffle bytes = one output per shard.
      * "identity"   — outputs stay shard-local (no shuffle).
    """

    mode: str = "all_gather"
    reduce_fn: Callable[[Any], Any] | None = None


class MapReduce:
    """Run a map_fn over data sharded along ``axis`` of a mesh.

    With ``mesh=None`` the engine runs the map_fn once over the whole input —
    the single-device path used by CPU tests and the paper-figure benchmarks
    (where per-"task" behaviour is simulated by slicing).
    """

    def __init__(self, mesh: Mesh | None = None, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.last_shuffle_bytes: int = 0

    # ------------------------------------------------------------------
    def run(
        self,
        map_fn: Callable[..., Any],
        combine: CombineSpec,
        *sharded_args: Any,
        replicated_args: tuple = (),
    ) -> Any:
        # Spans attach to the context tracer installed by the caller
        # (repro.serve installs its per-batch tracer around execute); with
        # the default NULL_TRACER nothing records.
        tracer = current_tracer()

        if self.mesh is None:
            with tracer.span("mapreduce", mode=combine.mode, shards=1) as mr:
                with tracer.span("map.shard", shard=0) as m_sp:
                    out = map_fn(*sharded_args, *replicated_args)
                # Identity combine keeps outputs shard-local: no shuffle,
                # same as the mesh path reports.
                if combine.mode == "identity":
                    self.last_shuffle_bytes = 0
                else:
                    with tracer.span("map.meter"):
                        self.last_shuffle_bytes = _tree_bytes(jax.eval_shape(
                            map_fn, *sharded_args, *replicated_args
                        ))
                m_sp.set(shuffle_bytes=self.last_shuffle_bytes)
                mr.set(shuffle_bytes=self.last_shuffle_bytes)
                if combine.mode == "all_gather":
                    with tracer.span("reduce"):
                        stacked = jax.tree_util.tree_map(
                            lambda x: x[None], out
                        )
                        return (
                            combine.reduce_fn(stacked)
                            if combine.reduce_fn else stacked
                        )
                if combine.mode == "psum":
                    with tracer.span("reduce"):
                        return (
                            combine.reduce_fn(out) if combine.reduce_fn
                            else out
                        )
                return out

        axis = self.axis
        n_shards = self.mesh.shape[axis]

        def shard_body(*args):
            shard_out = map_fn(*args)
            if combine.mode == "all_gather":
                gathered = jax.tree_util.tree_map(
                    lambda x: jax.lax.all_gather(x, axis), shard_out
                )
                if combine.reduce_fn is not None:
                    return combine.reduce_fn(gathered)
                return gathered
            if combine.mode == "psum":
                summed = jax.lax.psum(shard_out, axis)
                if combine.reduce_fn is not None:
                    return combine.reduce_fn(summed)
                return summed
            return shard_out

        in_specs = tuple(P(axis) for _ in sharded_args) + tuple(
            P() for _ in replicated_args
        )
        out_mode = combine.mode
        out_specs = P(axis) if out_mode == "identity" else P()

        fn = jax.shard_map(
            shard_body,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        with tracer.span("mapreduce", mode=out_mode, shards=n_shards) as mr:
            # Meter shuffle bytes: what each shard contributes to the
            # collective.
            with tracer.span("map.meter"):
                shard_args_shapes = []
                for a in sharded_args:
                    def _slice(x):
                        shape = (x.shape[0] // n_shards,) + x.shape[1:]
                        return jax.ShapeDtypeStruct(shape, x.dtype)
                    shard_args_shapes.append(
                        jax.tree_util.tree_map(_slice, a)
                    )
                per_shard = _tree_bytes(jax.eval_shape(
                    map_fn, *shard_args_shapes, *replicated_args
                ))
            self.last_shuffle_bytes = (
                per_shard * n_shards if out_mode != "identity" else 0
            )
            mr.set(shuffle_bytes=self.last_shuffle_bytes)
            if tracer.enabled:
                # One jit dispatch covers every shard on the mesh path, so
                # per-shard *time* can't be split honestly; attribute the
                # per-shard shuffle contribution as zero-duration events and
                # time the fused execution as one span.
                per = self.last_shuffle_bytes // n_shards if n_shards else 0
                for i in range(n_shards):
                    tracer.event("map.shard", shard=i, shuffle_bytes=per)
            with tracer.span("map+reduce.fused"):
                return fn(*sharded_args, *replicated_args)


def shard_leading(mesh: Mesh, axis: str, tree: Any) -> Any:
    """Device_put a host pytree with its leading dim sharded along ``axis``."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree
    )
