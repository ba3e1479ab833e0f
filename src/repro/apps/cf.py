"""User-based CF recommendation on the MapReduce engine (paper §III-D app 2).

Map shards hold disjoint user rows of the rating matrix.  For a batch of
active users, a map task computes Pearson weights against its users and
emits neighbourhood contributions; the reduce stage combines them into

    p(u,i) = r̄_u + Σ_v w(u,v)(r_vi − r̄_v) / Σ_v |w(u,v)| m_vi .

AccurateML's aggregation for CF stores, per LSH bucket g of users:

    sr_g[i] = Σ_{v∈g} m_vi r_vi          (raw rating sums -> centroid profile)
    s_g[i]  = Σ_{v∈g} m_vi (r_vi − r̄_v)  (centred sums -> numerator surrogate)
    c_g[i]  = Σ_{v∈g} m_vi               (rater counts -> denominator surrogate)

so a bucket's *entire* contribution is reconstructed from one centroid weight
(w(u, centroid_g) · s_g / |w| · c_g) — information from all users retained,
unlike sampling which discards rows.  Stage 2 replaces the top-correlated
buckets' surrogate with exact per-user terms.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import aggregate as agg_lib
from repro.core import correlation as corr_lib
from repro.core import engine as engine_lib
from repro.core import lsh as lsh_lib
from repro.core import refine as refine_lib
from repro.kernels import ops as kernel_ops
from repro.kernels.topk_stream import BIG  # shared sentinel: one definition
from repro.serve import servable as serve_servable
from repro.serve.request import ErrorBound


def user_means(ratings: jax.Array, mask: jax.Array) -> jax.Array:
    """Per-user mean over rated items. [U,I],[U,I] -> [U,1]."""
    return jnp.sum(ratings * mask, axis=1, keepdims=True) / jnp.maximum(
        jnp.sum(mask, axis=1, keepdims=True), 1.0
    )


# Significance weighting (Herlocker-style): weights from few co-rated items
# are unreliable; shrink by co/(co + SHRINK).  Applied identically to every
# processing path so the exact/approximate comparison stays fair.
SHRINK = 8.0


def shrink_weights(w: jax.Array, co_counts: jax.Array) -> jax.Array:
    return w * (co_counts / (co_counts + SHRINK))


# Error-bound calibration knobs: the claimed CF bound is
#     CF_BOUND_Z * mean_i( sqrt(Σ_g w_g² · SS_g[i]) / den[i] )
# (SS_g = within-bucket centred second moment of ratings; the surrogate's
# stderr under a within-bucket-iid model).  Z is tuned so the claim covers
# >= CF_BOUND_CONFIDENCE of observed |approx - exact| rating MAEs in
# ``benchmarks/error_bounds.py``.
CF_BOUND_Z = 3.0
CF_BOUND_CONFIDENCE = 0.9


# ---------------------------------------------------------------------------
# exact + sampled map tasks
# ---------------------------------------------------------------------------

@jax.jit
def exact_map(ratings, mask, active, active_mask):
    """Basic map task: Pearson weights vs all shard users; partial sums.

    Returns (num [Q,I], den [Q,I]) — the shard's neighbourhood contribution.
    """
    w = kernel_ops.cf_weights(active, active_mask, ratings, mask)  # [Q,U]
    co = active_mask @ mask.T                                      # [Q,U]
    w = shrink_weights(w, co)
    centred = (ratings - user_means(ratings, mask)) * mask
    num = w @ centred
    den = jnp.abs(w) @ mask
    return num, den


@partial(jax.jit, static_argnames=("n_sample",))
def sampled_map(ratings, mask, active, active_mask, sample_idx, *, n_sample):
    """Prior art: uniform subset of users."""
    sub_r = ratings[sample_idx[:n_sample]]
    sub_m = mask[sample_idx[:n_sample]]
    return exact_map(sub_r, sub_m, active, active_mask)


# ---------------------------------------------------------------------------
# AccurateML aggregation + two-stage map task
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CFAggregates:
    agg: agg_lib.AggregatedData   # index over users (perm/offsets/bucket_of)
    profile: jax.Array            # [K,I] centroid rating profile sr/c
    profile_mask: jax.Array       # [K,I] 1 where any bucket user rated i
    s: jax.Array                  # [K,I] centred sums
    c: jax.Array                  # [K,I] rater counts
    cvar: jax.Array               # [K,I] centred 2nd moment of ratings (SS)

    def tree_flatten(self):
        return (
            self.agg, self.profile, self.profile_mask, self.s, self.c,
            self.cvar,
        ), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)


@partial(jax.jit, static_argnames=("n_buckets",))
def _build_cf_aggregates(ratings, mask, ids, n_buckets):
    means = user_means(ratings, mask)
    centred = (ratings - means) * mask
    sr = jax.ops.segment_sum(ratings * mask, ids, num_segments=n_buckets)
    sr2 = jax.ops.segment_sum(
        jnp.square(ratings) * mask, ids, num_segments=n_buckets
    )
    s = jax.ops.segment_sum(centred, ids, num_segments=n_buckets)
    c = jax.ops.segment_sum(mask, ids, num_segments=n_buckets)
    counts = jax.ops.segment_sum(
        jnp.ones((ratings.shape[0],), jnp.int32), ids, num_segments=n_buckets
    )
    profile = sr / jnp.maximum(c, 1.0)
    profile_mask = (c > 0).astype(ratings.dtype)

    perm = jnp.argsort(ids, stable=True).astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    agg = agg_lib.AggregatedData(
        means=profile, counts=counts, perm=perm, offsets=offsets,
        bucket_of=ids.astype(jnp.int32),
    )
    return CFAggregates(
        agg=agg, profile=profile, profile_mask=profile_mask, s=s, c=c,
        cvar=agg_lib.centered_second_moment(sr, sr2, c),
    )


def build_cf_aggregates(
    ratings: jax.Array, mask: jax.Array, params: lsh_lib.LSHParams
) -> CFAggregates:
    """LSH-bucket users by centred rating profile; aggregate (§III-B)."""
    centred = (ratings - user_means(ratings, mask)) * mask
    ids = lsh_lib.bucket_ids(centred, params)
    return _build_cf_aggregates(ratings, mask, ids, params.config.n_buckets)


@partial(jax.jit, static_argnames=("n_buckets",))
def cf_mergeable_stats(
    ratings: jax.Array, mask: jax.Array, fine_ids: jax.Array, n_buckets: int
) -> dict[str, jax.Array]:
    """Additive per-bucket statistics for the aggregate store.

    ``sr`` (raw rating sums), ``sr2`` (raw squared-rating sums — the second
    moment behind the per-bucket rating variance that prices the error
    bound), ``s`` (centred sums), ``c`` (rater counts) and the user counts
    are all additive under bucket union, so a coarser pyramid level's
    centroid profile (sr/c), surrogate terms, and variance re-derive
    exactly from merged statistics.
    """
    centred = (ratings - user_means(ratings, mask)) * mask
    ones = jnp.ones((ratings.shape[0],), jnp.int32)
    return {
        "counts": jax.ops.segment_sum(ones, fine_ids, num_segments=n_buckets),
        "sr": jax.ops.segment_sum(
            ratings * mask, fine_ids, num_segments=n_buckets
        ),
        "sr2": jax.ops.segment_sum(
            jnp.square(ratings) * mask, fine_ids, num_segments=n_buckets
        ),
        "s": jax.ops.segment_sum(centred, fine_ids, num_segments=n_buckets),
        "c": jax.ops.segment_sum(mask, fine_ids, num_segments=n_buckets),
    }


@jax.jit
def cf_assemble(stats: dict, index: agg_lib.BucketIndex) -> CFAggregates:
    """Statistics + index -> the prepared aggregates ``accurateml_map`` uses.

    Snapshots that predate the second-moment statistics (no ``sr2`` entry)
    assemble with a saturated variance (finite BIG, not inf: cvar feeds a
    matmul where 0-weight x inf would poison the sum with NaN) so any
    answer touching them claims an unusably large bound — max uncertainty,
    never silent optimism.
    """
    c = stats["c"]
    profile = stats["sr"] / jnp.maximum(c, 1.0)
    agg = agg_lib.AggregatedData(
        means=profile, counts=stats["counts"], perm=index.perm,
        offsets=index.offsets, bucket_of=index.bucket_of,
    )
    if "sr2" in stats:
        cvar = agg_lib.centered_second_moment(stats["sr"], stats["sr2"], c)
    else:
        cvar = jnp.full(c.shape, BIG, profile.dtype)
    return CFAggregates(
        agg=agg, profile=profile, profile_mask=(c > 0).astype(profile.dtype),
        s=stats["s"], c=c, cvar=cvar,
    )


@partial(jax.jit, static_argnames=("refine_budget", "with_bound"))
def accurateml_map(
    ratings, mask, cf_agg: CFAggregates, active, active_mask,
    *, refine_budget: int, with_bound: bool = False,
):
    """Algorithm 1 for CF.  Correlation of bucket g for active user q is
    |w(q, centroid_g)| (paper: the weight to the aggregated user); each
    active user ranks and refines its own buckets (per-query Alg. 1).

    With ``with_bound=True`` a third output ``varsum`` [Q,I] is returned:
    Σ_g w_g² · SS_g[i] over the buckets still answered by surrogate (after
    refinement, covered buckets contribute exact terms — zero surrogate
    variance).  It is additive under the engine's psum, so the cross-shard
    stderr sqrt(varsum)/den is exact, not a per-shard approximation.
    """
    agg = cf_agg.agg
    # Named scopes put each device phase's ops under one op-name path
    # component (stage1, stage2.centroids/select/rows/merge), so the
    # compiled HLO names each op's phase; they are metadata and leave the
    # HLO ops as they are.
    # ---- stage 1: centroid weights + surrogate contribution ----
    # (the refined map repeats it, under its own scope)
    with jax.named_scope(
        "stage1" if refine_budget <= 0 else "stage2.centroids"
    ):
        w_g = kernel_ops.cf_weights(
            active, active_mask, cf_agg.profile, cf_agg.profile_mask
        )                                                # [Q,K]
        co_g = active_mask @ cf_agg.profile_mask.T
        w_g = shrink_weights(w_g, co_g)
        w_g = jnp.where(agg.counts[None, :] > 0, w_g, 0.0)
        num = w_g @ cf_agg.s                             # [Q,I]
        den = jnp.abs(w_g) @ cf_agg.c

    if refine_budget <= 0:
        with jax.named_scope("stage1"):
            if not with_bound:
                return num, den
            varsum = jnp.square(w_g) @ cf_agg.cvar       # [Q,I]
            return num, den, varsum

    # ---- stage 2: per-query replacement of top buckets by exact users ----
    with jax.named_scope("stage2.select"):
        corr = jnp.abs(w_g)                              # [Q,K]
        rankings = corr_lib.rank_buckets_multi(corr, agg.counts)
        idx, valid = jax.vmap(
            lambda r: agg_lib.refinement_indices(agg, r, refine_budget)
        )(rankings)                                      # [Q,B] x2
        covered = jax.vmap(
            lambda r: agg_lib.buckets_fully_covered(agg, r, refine_budget)
        )(rankings)                                      # [Q,K]
        covered = covered & (agg.counts[None, :] > 0)

        # Exact sums must not double-count: only users of fully covered
        # buckets (per query) replace their bucket's surrogate.
        use = valid & jnp.take_along_axis(
            covered, agg.bucket_of[idx], axis=1
        )                                                # [Q,B]
    # Gather-free neighbour selection: the scalar-prefetch kernel reads each
    # selected user's centred/mask rows straight from HBM, forms the shrunk
    # Pearson weight in registers, and accumulates the weighted sums — the
    # [Q,B,I] gathered tensors never materialize.
    with jax.named_scope("stage2.rows"):
        _, num_delta, den_delta = kernel_ops.cf_refine(
            active, active_mask, ratings, mask, idx, use, shrink=SHRINK
        )

    # Subtract the covered buckets' surrogate, add their exact terms.
    with jax.named_scope("stage2.merge"):
        w_g_cov = jnp.where(covered, w_g, 0.0)
        num = num - w_g_cov @ cf_agg.s + num_delta
        den = den - jnp.abs(w_g_cov) @ cf_agg.c + den_delta
        if not with_bound:
            return num, den
        # Surrogate variance only over the *unrefined* buckets: covered
        # ones were replaced by exact per-user terms and carry no
        # surrogate error.
        w_g_unc = jnp.where(covered, 0.0, w_g)
        varsum = jnp.square(w_g_unc) @ cf_agg.cvar
        return num, den, varsum


# ---------------------------------------------------------------------------
# reduce + metrics
# ---------------------------------------------------------------------------

def predict(num, den, active, active_mask):
    """Reduce stage: combine (psum'd) partial sums into predictions [Q,I]."""
    base = user_means(active, active_mask)
    return jnp.where(den > 1e-8, base + num / jnp.maximum(den, 1e-8), base)


def rmse(pred, truth, test_mask) -> float:
    err = (pred - truth) * test_mask
    n = jnp.maximum(jnp.sum(test_mask), 1.0)
    return float(jnp.sqrt(jnp.sum(err * err) / n))


def rmse_loss(rmse_exact: float, rmse_approx: float) -> float:
    """Paper metric: increased prediction error / exact error."""
    return max(0.0, (rmse_approx - rmse_exact) / max(rmse_exact, 1e-12))


# ---------------------------------------------------------------------------
# end-to-end jobs (sharded loop on host; the pod path uses core.engine)
# ---------------------------------------------------------------------------

def _shard_slices(n, n_shards):
    return [
        slice(s * n // n_shards, (s + 1) * n // n_shards)
        for s in range(n_shards)
    ]


def run_exact(ratings, mask, active, active_mask, *, n_shards: int = 1):
    num = den = 0.0
    for sl in _shard_slices(ratings.shape[0], n_shards):
        n_, d_ = exact_map(ratings[sl], mask[sl], active, active_mask)
        num, den = num + n_, den + d_
    return predict(num, den, active, active_mask)


def run_accurateml(
    ratings, mask, active, active_mask, *, compression_ratio: float,
    eps_max: float, lsh_key: jax.Array, n_shards: int = 1,
    n_hashes: int = 4, bucket_width: float = 8.0,
):
    num = den = 0.0
    for s, sl in enumerate(_shard_slices(ratings.shape[0], n_shards)):
        r_, m_ = ratings[sl], mask[sl]
        cfg = lsh_lib.config_for_compression(
            r_.shape[0], compression_ratio, n_hashes=n_hashes,
            bucket_width=bucket_width,
        )
        params = lsh_lib.init_lsh(
            jax.random.fold_in(lsh_key, s), r_.shape[1], cfg
        )
        cf_agg = build_cf_aggregates(r_, m_, params)
        budget = refine_lib.eps_to_budget(r_.shape[0], eps_max)
        n_, d_ = accurateml_map(
            r_, m_, cf_agg, active, active_mask, refine_budget=budget
        )
        num, den = num + n_, den + d_
    return predict(num, den, active, active_mask)


def run_sampled(
    ratings, mask, active, active_mask, *, sample_frac: float,
    sample_key: jax.Array, n_shards: int = 1,
):
    num = den = 0.0
    for s, sl in enumerate(_shard_slices(ratings.shape[0], n_shards)):
        r_, m_ = ratings[sl], mask[sl]
        ns = max(1, int(sample_frac * r_.shape[0]))
        perm = jax.random.permutation(
            jax.random.fold_in(sample_key, s), r_.shape[0]
        )
        n_, d_ = sampled_map(r_, m_, active, active_mask, perm, n_sample=ns)
        num, den = num + n_, den + d_
    return predict(num, den, active, active_mask)


# ---------------------------------------------------------------------------
# serving adapter (repro.serve.Servable)
# ---------------------------------------------------------------------------

class CFServable(serve_servable.LSHServableBase):
    """CF recommendation behind the ``repro.serve.Servable`` protocol.

    One instance holds one neighbourhood shard (user rows of the rating
    matrix).  Request payload: ``(active_row [I], active_mask_row [I])`` for
    one active user; answer: predicted rating row [I] (numpy).  ``run``
    executes ``accurateml_map`` through the MapReduce engine with a psum
    combine into ``predict``.
    """

    name = "cf"

    def __init__(
        self,
        ratings: jax.Array,
        mask: jax.Array,
        *,
        lsh_key: jax.Array,
        n_hashes: int = 4,
        bucket_width: float = 8.0,
        engine: engine_lib.MapReduce | None = None,
        store=None,
        pyramid_spec=None,
    ):
        super().__init__(
            (ratings, mask), lsh_key=lsh_key, n_hashes=n_hashes,
            bucket_width=bucket_width, engine=engine, store=store,
            pyramid_spec=pyramid_spec,
        )
        self.ratings = ratings
        self.mask = mask

    # --- repro.store pyramid hooks ---
    def hash_features(self) -> jax.Array:
        return (self.ratings - user_means(self.ratings, self.mask)) * self.mask

    def mergeable_stats(self, fine_ids, n_buckets):
        return cf_mergeable_stats(self.ratings, self.mask, fine_ids, n_buckets)

    def assemble(self, stats, index) -> CFAggregates:
        return cf_assemble(stats, index)

    def probe_payload(self) -> tuple:
        return (self.ratings[0], self.mask[0])

    def pad_batch(self, payloads, batch: int) -> tuple:
        return self.stack_pad(payloads, batch)

    def run(
        self, prepared: CFAggregates, batch_payload: tuple,
        *, refine_budget: int,
    ) -> jax.Array:
        active, active_mask = batch_payload
        map_fn = partial(
            accurateml_map, refine_budget=refine_budget, with_bound=True
        )

        def reduce_fn(nd):
            # nd = psum'd (num, den, varsum): both the prediction and the
            # surrogate stderr are exact cross-shard (all three additive).
            pred = predict(nd[0], nd[1], active, active_mask)
            stderr = jnp.where(
                nd[1] > 1e-8, jnp.sqrt(nd[2]) / jnp.maximum(nd[1], 1e-8), 0.0
            )
            return pred, CF_BOUND_Z * jnp.mean(stderr, axis=-1)

        combine = engine_lib.CombineSpec(mode="psum", reduce_fn=reduce_fn)
        return self.engine.run(
            map_fn, combine, self.ratings, self.mask,
            replicated_args=(prepared, active, active_mask),
        )

    def unpack(self, outputs: tuple, n: int) -> list:
        return list(np.asarray(outputs[0][:n]))

    def error_bounds(self, stage1_out, n: int) -> list:
        """Per-user claimed bound on the mean absolute rating error."""
        bounds = np.asarray(stage1_out[1][:n])
        return [
            ErrorBound(
                value=float(b),
                metric="rating_mae",
                confidence=CF_BOUND_CONFIDENCE,
            )
            for b in bounds
        ]

    def accuracy_proxy(self, stage1_out, refined_out, n: int) -> list[float]:
        """Mean absolute rating delta per active user, stage-1 vs refined.

        0.0 = refinement left the predicted rating row unchanged; larger
        values mean the aggregated answer was further from the refined one
        (in rating units) — the serving-path analogue of the paper's
        prediction-error metric.
        """
        s1 = np.asarray(stage1_out[0][:n], dtype=np.float64)
        s2 = np.asarray(refined_out[0][:n], dtype=np.float64)
        return [float(v) for v in np.mean(np.abs(s2 - s1), axis=-1)]


# ---------------------------------------------------------------------------
# shuffle-cost model (paper Fig. 5 semantics)
# ---------------------------------------------------------------------------

def shuffle_bytes_exact(n_users: int, n_items: int, n_active: int) -> int:
    """Basic job: map emits each neighbour's (weight, centred row, mask row)."""
    return 4 * (n_active * n_users + 2 * n_users * n_items)


def shuffle_bytes_accurateml(
    n_users: int, n_items: int, n_active: int,
    compression_ratio: float, eps_max: float,
) -> int:
    """AccurateML job: neighbours = K centroids + refined originals."""
    k = int(round(n_users / compression_ratio))
    b = int(jnp.ceil(eps_max * n_users))
    n_neigh = k + b
    return 4 * (n_active * n_neigh + 2 * n_neigh * n_items)
