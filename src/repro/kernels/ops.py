"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy:
  * On TPU backends: call the Pallas kernel (compiled).
  * Elsewhere (this container is CPU): call the pure-jnp reference, which is
    bit-compatible with the kernels (kernel tests run the Pallas bodies in
    interpret mode against the same reference).

``force`` lets tests pin a path: "pallas_interpret" runs the real kernel
body under the Pallas interpreter on CPU.  The ``REPRO_FORCE_KERNELS``
environment variable (read once at import: ``ref`` or ``pallas_interpret``)
sets the default for every call that doesn't pass ``force`` explicitly, so
CI on CPU can exercise the real kernel bodies without threading ``force=``
through every call site.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref

_FORCE_CHOICES = (None, "ref", "pallas_interpret")
_FORCE_DEFAULT = os.environ.get("REPRO_FORCE_KERNELS") or None
if _FORCE_DEFAULT not in _FORCE_CHOICES:
    raise ValueError(
        f"REPRO_FORCE_KERNELS={_FORCE_DEFAULT!r}: expected one of "
        f"{_FORCE_CHOICES[1:]}"
    )


def _resolve(force: str | None) -> str | None:
    return force if force is not None else _FORCE_DEFAULT


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def dispatch_path(force: str | None = None) -> str:
    """The path a call with this ``force`` takes: ref/pallas_interpret/pallas."""
    force = _resolve(force)
    if force is not None:
        return force
    return "pallas" if _on_tpu() else "ref"


# ---------------------------------------------------------------------------
# observability hook (repro.obs.probes.KernelProbe)
#
# When a probe is installed, host-level op calls are timed around
# block_until_ready and recorded (measured p50 per kernel path); calls made
# while an outer jit is tracing are passed through untouched.  With no probe
# the wrappers cost one ``is None`` test — the hot path stays lean.
# ---------------------------------------------------------------------------

_PROBE = None


def set_probe(probe) -> None:
    global _PROBE
    _PROBE = probe


def get_probe():
    return _PROBE


def _probed(op_name: str):
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            probe = _PROBE
            if probe is None:
                return fn(*args, **kwargs)
            return probe.timed(op_name, fn, args, kwargs)
        return wrapper
    return deco


@_probed("knn_distance")
@functools.partial(jax.jit, static_argnames=("force",))
def knn_distance(
    queries: jax.Array, points: jax.Array, *, force: str | None = None
) -> jax.Array:
    """Squared-L2 distance matrix [Q,N]; MXU-tiled Pallas kernel on TPU."""
    force = _resolve(force)
    if force == "ref":
        return ref.knn_distance(queries, points)
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import knn_distance as kk
        return kk.knn_distance_pallas(
            queries, points, interpret=force == "pallas_interpret"
        )
    return ref.knn_distance(queries, points)


@_probed("lsh_hash")
@functools.partial(jax.jit, static_argnames=("width", "force"))
def lsh_hash(
    data: jax.Array, a: jax.Array, b: jax.Array, width: float,
    *, force: str | None = None,
) -> jax.Array:
    """Fused projection+floor p-stable hash, [N,H] int32."""
    force = _resolve(force)
    if force == "ref":
        return ref.lsh_hash(data, a, b, width)
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import lsh_hash as lk
        return lk.lsh_hash_pallas(
            data, a, b, width, interpret=force == "pallas_interpret"
        )
    return ref.lsh_hash(data, a, b, width)


@_probed("cf_weights")
@functools.partial(jax.jit, static_argnames=("force",))
def cf_weights(
    active: jax.Array, active_mask: jax.Array,
    users: jax.Array, users_mask: jax.Array,
    *, force: str | None = None,
) -> jax.Array:
    """Masked Pearson weight matrix [Q,U]."""
    force = _resolve(force)
    if force == "ref":
        return ref.cf_weights(active, active_mask, users, users_mask)
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import cf_weights as ck
        return ck.cf_weights_pallas(
            active, active_mask, users, users_mask,
            interpret=force == "pallas_interpret",
        )
    return ref.cf_weights(active, active_mask, users, users_mask)


@_probed("aggregated_attention_decode")
@functools.partial(jax.jit, static_argnames=("scale", "force"))
def aggregated_attention_decode(
    q, k_cache, v_cache, bucket_of, mean_k, mean_v, counts, refined,
    *, scale: float, valid_len=None, force: str | None = None,
):
    """Two-stage (centroid + refined-bucket) decode attention, [H,d]."""
    force = _resolve(force)
    if force == "ref":
        return ref.aggregated_attention_decode(
            q, k_cache, v_cache, bucket_of, mean_k, mean_v, counts,
            refined, scale, valid_len,
        )
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import aggregated_attention as ak
        return ak.aggregated_attention_pallas(
            q, k_cache, v_cache, bucket_of, mean_k, mean_v, counts,
            refined, scale=scale, valid_len=valid_len,
            interpret=force == "pallas_interpret",
        )
    return ref.aggregated_attention_decode(
        q, k_cache, v_cache, bucket_of, mean_k, mean_v, counts, refined,
        scale, valid_len,
    )


# ---------------------------------------------------------------------------
# fused two-stage hot-path kernels (streaming top-k + gather-free refine)
# ---------------------------------------------------------------------------

@_probed("distance_topk")
@functools.partial(jax.jit, static_argnames=("k", "metric", "force"))
def distance_topk(
    queries: jax.Array, points: jax.Array, labels: jax.Array,
    valid: jax.Array | None = None,
    *, k: int, metric: str = "l2", force: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused score + streaming top-k: -> ([Q,k] scores, [Q,k] labels).

    ``metric="l2"`` scores squared-L2 distance; ``metric="dot"`` scores
    *negated* dot-product correlation (decode-side stage-1 bucket
    selection), so the k smallest scores are the k most correlated.  The
    [Q,N] score matrix never reaches HBM on the kernel path; the running
    k-best lives in VMEM scratch across point tiles.
    """
    force = _resolve(force)
    if force == "ref":
        return ref.distance_topk(queries, points, labels, valid,
                                 k=k, metric=metric)
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import distance_topk as dk
        return dk.distance_topk_pallas(
            queries, points, labels, valid, k=k, metric=metric,
            interpret=force == "pallas_interpret",
        )
    return ref.distance_topk(queries, points, labels, valid,
                             k=k, metric=metric)


@_probed("candidate_topk")
@functools.partial(jax.jit, static_argnames=("k", "force"))
def candidate_topk(
    dists: jax.Array, labels: jax.Array,
    init_d: jax.Array | None = None, init_l: jax.Array | None = None,
    *, k: int, force: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Streaming top-k over precomputed [Q,M] candidates, optionally seeded
    with a previous [Q,k] running best (the fused stage-2 finalize and the
    pairwise shard merge both chain selections through this seed)."""
    force = _resolve(force)
    if force == "ref":
        return ref.candidate_topk(dists, labels, init_d, init_l, k=k)
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import topk_stream as ts
        if init_d is None:
            init_d = jnp.full(dists.shape[:1] + (k,), ts.BIG, jnp.float32)
            init_l = jnp.zeros(dists.shape[:1] + (k,), jnp.int32)
        return ts.candidate_topk_pallas(
            dists, labels, init_d, init_l, k=k,
            interpret=force == "pallas_interpret",
        )
    return ref.candidate_topk(dists, labels, init_d, init_l, k=k)


@_probed("agg_refine_attention")
@functools.partial(jax.jit, static_argnames=("scale", "force"))
def agg_refine_attention(
    q: jax.Array, k_slots: jax.Array, v_slots: jax.Array,
    counts: jax.Array, top_idx: jax.Array, use: jax.Array,
    *, scale: float, force: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stage-2 exact re-attention over selected KV buckets: the partial
    softmax triple (m, l, acc), merged with the centroid pass via
    ``ref.merge_partials``.  Scalar-prefetch row walk on the kernel path —
    the gathered [B,R,C,...] slot tensor never exists."""
    force = _resolve(force)
    if force == "ref":
        return ref.agg_refine_attention(
            q, k_slots, v_slots, counts, top_idx, use, scale
        )
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import agg_refine as ar
        return ar.agg_refine_attention_pallas(
            q, k_slots, v_slots, counts, top_idx, use, scale=scale,
            interpret=force == "pallas_interpret",
        )
    return ref.agg_refine_attention(
        q, k_slots, v_slots, counts, top_idx, use, scale
    )


@_probed("refine_distances")
@functools.partial(jax.jit, static_argnames=("force",))
def refine_distances(
    queries: jax.Array, train_x: jax.Array,
    idx: jax.Array, valid: jax.Array,
    *, force: str | None = None,
) -> jax.Array:
    """Gather-free stage-2 exact distances: [Q,B] with BIG-masked padding."""
    force = _resolve(force)
    if force == "ref":
        return ref.refine_distances(queries, train_x, idx, valid)
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import refine_distances as rd
        return rd.refine_distances_pallas(
            queries, train_x, idx, valid,
            interpret=force == "pallas_interpret",
        )
    return ref.refine_distances(queries, train_x, idx, valid)


@_probed("cf_refine")
@functools.partial(jax.jit, static_argnames=("shrink", "force"))
def cf_refine(
    active: jax.Array, active_mask: jax.Array,
    ratings: jax.Array, mask: jax.Array,
    idx: jax.Array, use: jax.Array,
    *, shrink: float, force: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gather-free CF refinement: (w_ref [Q,B], num_delta, den_delta)."""
    force = _resolve(force)
    if force == "ref":
        return ref.cf_refine(
            active, active_mask, ratings, mask, idx, use, shrink=shrink
        )
    if force == "pallas_interpret" or _on_tpu():
        from repro.kernels import cf_refine as cr
        return cr.cf_refine_pallas(
            active, active_mask, ratings, mask, idx, use, shrink=shrink,
            interpret=force == "pallas_interpret",
        )
    return ref.cf_refine(
        active, active_mask, ratings, mask, idx, use, shrink=shrink
    )
