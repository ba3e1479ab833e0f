"""kNN classification on the MapReduce engine (paper §III-D application 1).

Three processing paths share one combine (= reduce) stage:

  * ``exact``      — scan all original points (basic map task),
  * ``sampled``    — scan a uniform subset (the compared prior art, §IV-C),
  * ``accurateml`` — Algorithm 1: distances to aggregated points first, then
                     exact distances for the top-correlated buckets only.

Each map shard outputs its local top-k (distance, label) per test point —
the "fixed outputs" the paper notes for kNN — and the reduce stage merges
shard-local top-k sets into the global top-k, then majority-votes.
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
from repro.kernels.refine_distances import row_table
from repro.kernels.topk_stream import BIG  # shared sentinel: one definition
from repro.serve import servable as serve_servable
from repro.serve.request import ErrorBound

# Chebyshev-style slack on the spread/gap displacement probability (both in
# squared-distance units): scales how aggressively within-bucket spread is
# assumed to displace a selected neighbour past the top-k boundary.
# Calibrated against exact answers by benchmarks/error_bounds.py (claimed
# coverage must stay >= 0.9).
KNN_BOUND_SLACK = 1.0
KNN_BOUND_CONFIDENCE = 0.9


# ---------------------------------------------------------------------------
# distance + vote primitives
# ---------------------------------------------------------------------------

def pairwise_sq_dists(queries: jax.Array, points: jax.Array) -> jax.Array:
    """[Q,D] x [N,D] -> [Q,N] squared L2.  Hot spot: Pallas kernel on TPU."""
    return kernel_ops.knn_distance(queries, points)


def local_topk(
    dists: jax.Array, labels: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Per-query k smallest distances + their labels.

    [Q,N],[N] -> [Q,k] x2 (shared label row), or [Q,N],[Q,N] -> [Q,k] x2.
    """
    neg, idx = jax.lax.top_k(-dists, k)
    if labels.ndim == dists.ndim:
        picked = jnp.take_along_axis(labels, idx, axis=-1)
    else:
        picked = labels[idx]
    return -neg, picked


def majority_vote(
    topk_dists: jax.Array, topk_labels: jax.Array, n_classes: int
) -> jax.Array:
    """Majority class among valid (finite-distance) neighbours."""
    valid = (topk_dists < BIG / 2).astype(jnp.float32)
    onehot = jax.nn.one_hot(topk_labels, n_classes) * valid[..., None]
    return jnp.argmax(jnp.sum(onehot, axis=-2), axis=-1).astype(jnp.int32)


def merge_topk(
    gathered_dists: jax.Array, gathered_labels: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """[S,Q,k] shard-local top-k -> [Q,k] global top-k (the reduce stage).

    Folds shards pairwise through the seeded streaming selection instead of
    materializing the [Q, S*k] moveaxis/reshape copies: shard s's k-best
    merges into the running best of shards 0..s-1.  Equivalent to one top_k
    over the flattened candidates (same (value, shard-order) tie-break).
    """
    s = gathered_dists.shape[0]
    d, l = gathered_dists[0], gathered_labels[0]
    if s == 1 or d.shape[-1] != k:
        d, l = local_topk(d, l, k)  # sort/trim so the seed is a [Q,k] best
    for i in range(1, s):
        d, l = kernel_ops.candidate_topk(
            gathered_dists[i], gathered_labels[i], d, l, k=k
        )
    return d, l


# ---------------------------------------------------------------------------
# map-task variants
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k",))
def exact_map(train_x, train_y, test_x, *, k: int):
    """Basic map task: all original points (paper Fig. 2a).

    Fused distance+top-k: point tiles stream through VMEM and fold into a
    running k-best, so the [Q, N] distance matrix never touches HBM.
    """
    return kernel_ops.distance_topk(test_x, train_x, train_y, k=k)


@partial(jax.jit, static_argnames=("k", "n_sample"))
def sampled_map(train_x, train_y, test_x, sample_idx, *, k: int, n_sample: int):
    """Prior-art approximation: uniform subset of ``n_sample`` points."""
    sub_x = train_x[sample_idx[:n_sample]]
    sub_y = train_y[sample_idx[:n_sample]]
    return kernel_ops.distance_topk(test_x, sub_x, sub_y, k=k)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KNNAggregates:
    """Aggregated training shard: centroids + bucket-majority labels.

    ``spread`` and ``dispersion`` are derived from the second-moment
    sufficient statistics (feature sumsq, label histogram) and feed the
    per-query stage-1 error bound; both are +inf on empty buckets.
    """

    agg: agg_lib.AggregatedData
    bucket_labels: jax.Array  # [K] majority label per bucket
    spread: jax.Array         # [K] within-bucket E‖x − μ‖² (+inf if empty)
    dispersion: jax.Array     # [K] 1 − majority-label fraction (+inf if empty)

    def tree_flatten(self):
        return (
            self.agg, self.bucket_labels, self.spread, self.dispersion
        ), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)


def build_knn_aggregates(
    train_x: jax.Array, train_y: jax.Array, params: lsh_lib.LSHParams,
    n_classes: int,
) -> KNNAggregates:
    ids = lsh_lib.bucket_ids(train_x, params)
    n_buckets = params.config.n_buckets
    agg = agg_lib.aggregate_by_bucket(train_x, ids, n_buckets)
    label_hist = jax.ops.segment_sum(
        jax.nn.one_hot(train_y, n_classes),
        ids,
        num_segments=n_buckets,
    )
    bucket_labels = jnp.argmax(label_hist, axis=-1).astype(jnp.int32)
    sums = jax.ops.segment_sum(
        train_x.astype(jnp.float32), ids, num_segments=n_buckets
    )
    sumsq = agg_lib.bucket_sumsq(train_x, ids, n_buckets)
    return KNNAggregates(
        agg=agg,
        bucket_labels=bucket_labels,
        spread=agg_lib.bucket_spread(sums, sumsq, agg.counts),
        dispersion=agg_lib.histogram_dispersion(label_hist),
    )


@partial(jax.jit, static_argnames=("n_buckets", "n_classes"))
def knn_mergeable_stats(
    train_x: jax.Array, train_y: jax.Array, fine_ids: jax.Array,
    n_buckets: int, n_classes: int,
) -> dict[str, jax.Array]:
    """Additive per-bucket sufficient statistics for the aggregate store.

    Feature sums, per-feature sums of squares, point counts, and the label
    histogram are all additive under bucket union, so every coarser pyramid
    level merges exactly (weighted means, majority labels, and the
    error-bound spread/dispersion re-derive from the merged stats).
    """
    ones = jnp.ones((train_x.shape[0],), dtype=jnp.int32)
    return {
        "counts": jax.ops.segment_sum(ones, fine_ids, num_segments=n_buckets),
        "sums": jax.ops.segment_sum(
            train_x.astype(jnp.float32), fine_ids, num_segments=n_buckets
        ),
        "sumsq": agg_lib.bucket_sumsq(train_x, fine_ids, n_buckets),
        "label_hist": jax.ops.segment_sum(
            jax.nn.one_hot(train_y, n_classes), fine_ids,
            num_segments=n_buckets,
        ),
    }


@jax.jit
def knn_assemble(stats: dict, index: agg_lib.BucketIndex) -> KNNAggregates:
    """Statistics + index -> the prepared aggregates ``accurateml_map`` uses.

    Snapshots written before the second-moment statistics existed restore
    without a ``sumsq`` entry; the spread then degrades to +inf everywhere
    (maximum uncertainty — the conservative direction), never to 0.
    """
    counts = stats["counts"]
    means = stats["sums"] / jnp.maximum(
        counts[:, None].astype(jnp.float32), 1.0
    )
    agg = agg_lib.AggregatedData(
        means=means, counts=counts, perm=index.perm, offsets=index.offsets,
        bucket_of=index.bucket_of,
    )
    labels = jnp.argmax(stats["label_hist"], axis=-1).astype(jnp.int32)
    if "sumsq" in stats:
        spread = agg_lib.bucket_spread(stats["sums"], stats["sumsq"], counts)
    else:
        spread = jnp.full(counts.shape, jnp.inf, jnp.float32)
    return KNNAggregates(
        agg=agg,
        bucket_labels=labels,
        spread=spread,
        dispersion=agg_lib.histogram_dispersion(stats["label_hist"]),
    )


def _vote_bound(
    d: jax.Array, lab: jax.Array, spread_sel: jax.Array,
    disp_sel: jax.Array, k: int, hidden: jax.Array | None = None,
) -> jax.Array:
    """[Q,k+1] selected distances/labels + per-candidate spread/dispersion
    -> [Q] claimed upper bound on the answer's label divergence from exact.

    Per kept neighbour i the bound prices two failure modes:

      * *displacement that matters*: the within-bucket spread of its own
        bucket plus the first excluded candidate's (either side moving
        closes the gap), against the squared-distance gap to that excluded
        candidate, scaled by the label-disagreement rate among the selected
        candidates — a neighbour displaced by a same-label competitor
        leaves the vote's label multiset unchanged, which is what makes
        the bound *tight* on well-separated data instead of saturating;
      * *relabeling*: the bucket's label-histogram dispersion (the
        centroid's majority label can be wrong even at exact distance).

    ``hidden`` ([Q], refined path only) adds the residual risk that an
    *unselected* unrefined bucket hides a true neighbour — after stage 2
    the kept candidates can all be exact originals (zero spread) while
    a never-refined bucket whose centroid sits within spread-reach of
    the kept radius still conceals error; without this term the claim
    collapses to ~0 while the true divergence does not.

    Candidates with spread/dispersion +inf (empty buckets, pre-second-moment
    snapshots) and BIG-padded slots saturate to probability 1 — unknown
    uncertainty can never claim a tight bound.
    """
    gap = jnp.maximum(d[:, k:k + 1] - d[:, :k], 0.0)          # [Q,k]
    sp, dp = spread_sel[:, :k], disp_sel[:, :k]
    valid = d < BIG / 2                                       # [Q,k+1]
    same = (lab[:, None, :] == lab[:, :, None]) & valid[:, None, :]
    n_valid = jnp.maximum(jnp.sum(valid, axis=-1, keepdims=True), 1)
    label_diff = 1.0 - jnp.sum(same, axis=-1) / n_valid       # [Q,k+1]
    comp = spread_sel[:, k:k + 1]                             # [Q,1]
    comp = jnp.where(
        valid[:, k:k + 1] & jnp.isfinite(comp), comp, 0.0
    )
    p_disp = jnp.minimum(
        KNN_BOUND_SLACK * (sp + comp) / jnp.maximum(gap, 1e-12), 1.0
    )
    p = jnp.clip(p_disp * label_diff[:, :k] + dp, 0.0, 1.0)
    p = jnp.where(jnp.isinf(sp), 1.0, p)                      # unknown bucket
    p = jnp.where(valid[:, :k], p, 1.0)                       # padded slot
    bound = jnp.mean(p, axis=-1)
    if hidden is not None:
        kept_diff = jnp.where(valid[:, :k], label_diff[:, :k], 0.0)
        bound = jnp.clip(
            bound + hidden * jnp.max(kept_diff, axis=-1), 0.0, 1.0
        )
    return bound


def _hidden_risk(
    d_cent_masked: jax.Array, spread: jax.Array, bid: jax.Array,
    d_radius: jax.Array, n_k: int,
) -> jax.Array:
    """[Q] risk that an unselected, unrefined bucket hides a true neighbour.

    A bucket that survived neither refinement (masked to BIG) nor the
    candidate top-k can still conceal points inside the kept radius when
    its centroid distance minus its spread undercuts ``d_radius`` (the
    first excluded candidate's distance).  Empty buckets are already BIG
    in ``d_cent_masked``; the exact-candidate sentinel ``n_k`` never
    matches a real bucket id.
    """
    sel = jnp.any(
        bid[:, :, None] == jnp.arange(n_k, dtype=bid.dtype)[None, None, :],
        axis=1,
    )                                                         # [Q,K]
    live = (d_cent_masked < BIG / 2) & ~sel
    margin = jnp.maximum(d_cent_masked - d_radius[:, None], 1e-12)
    risk = jnp.minimum(KNN_BOUND_SLACK * spread[None, :] / margin, 1.0)
    return jnp.max(jnp.where(live, risk, 0.0), axis=-1)


@partial(jax.jit, static_argnames=("k", "refine_budget", "with_bound"))
def accurateml_map(
    train_x: jax.Array,
    train_y: jax.Array,
    knn_agg: KNNAggregates,
    test_x: jax.Array,
    *,
    k: int,
    refine_budget: int,
    with_bound: bool = False,
):
    """Algorithm 1 instantiated for kNN (per test-point refinement ranking).

    Stage 1: distances from every test point to every *aggregated* point.
    Correlation of bucket i (Definition 4): c_i = -dist(test, centroid_i).

    Stage 2 (paper-faithful, per query): each test point ranks buckets by
    its own correlations and refines the top buckets until ``refine_budget``
    original points were processed *for that query* (Alg. 1 runs per test
    point).  Refined buckets' centroids are masked out of the candidate set
    (replace, not double-count); final output is a joint top-k over
    [unrefined centroids ∪ refined originals], chained through one running
    k-best (centroids seed it, refined candidates fold in) instead of a
    concatenate + top_k tail.

    With ``with_bound=True`` the output gains a per-query error bound
    ([Q], see ``_vote_bound``) and returns ``(d, labels, bound)``.  The
    selection then runs at k+1 internally (the bound needs the gap to the
    first excluded candidate) and carries each candidate's *bucket id*
    through the top-k merges packed next to its label
    (``label * (K+1) + bucket``; refined originals use the exact-candidate
    sentinel bucket K, which has zero spread/dispersion), so provenance
    survives the streaming merges without a second kernel pass.

    Only stage 2 reads ``train_x``: the [N, D] table, or its row table
    (``kernels.refine_distances.row_table``), which the row walk reads
    without a relayout.
    """
    agg = knn_agg.agg
    n_k = agg.means.shape[0]                                  # K (static)
    kk = k + 1 if with_bound else k
    # Named scopes put each device phase's ops under one op-name path
    # component (stage1, stage2.centroids/select/rows/merge), so the
    # compiled HLO names each op's phase; they are metadata and leave the
    # HLO ops as they are.
    if with_bound:
        # Pack (label, bucket) into one int32 label channel; spread and
        # dispersion gain a zero slot at index K for exact candidates.
        with jax.named_scope(
            "stage1" if refine_budget <= 0 else "stage2.merge"
        ):
            cent_ids = jnp.arange(n_k, dtype=jnp.int32)
            cent_comb = knn_agg.bucket_labels * jnp.int32(n_k + 1) + cent_ids
            spread_ext = jnp.concatenate(
                [knn_agg.spread, jnp.zeros((1,), jnp.float32)]
            )
            disp_ext = jnp.concatenate(
                [knn_agg.dispersion, jnp.zeros((1,), jnp.float32)]
            )

    if refine_budget <= 0:
        # Pure stage 1: fused distance+top-k over the aggregated points —
        # the [Q, K] matrix is never needed (no ranking to derive from it).
        with jax.named_scope("stage1"):
            if not with_bound:
                return kernel_ops.distance_topk(
                    test_x, agg.means, knn_agg.bucket_labels,
                    agg.counts > 0, k=k,
                )
            d, comb = kernel_ops.distance_topk(
                test_x, agg.means, cent_comb, agg.counts > 0, k=kk
            )
            bid = comb % jnp.int32(n_k + 1)
            labels = comb // jnp.int32(n_k + 1)
            bound = _vote_bound(d, labels, spread_ext[bid], disp_ext[bid], k)
            return d[:, :k], labels[:, :k], bound

    # ---- stage 1: initial output + correlations from aggregated points ----
    # The full [Q, K] distances are inherent here: every bucket needs a
    # correlation for the per-query refinement ranking (Alg. 1 line 2).
    with jax.named_scope("stage2.centroids"):
        d_cent = pairwise_sq_dists(test_x, agg.means)        # [Q, K]
        d_cent = jnp.where(agg.counts[None, :] > 0, d_cent, BIG)

    # ---- stage 2: per-query refinement of the top-correlated buckets ----
    with jax.named_scope("stage2.select"):
        corr = -d_cent                                       # [Q, K]
        rankings = corr_lib.rank_buckets_multi(corr, agg.counts)  # [Q, K]
        idx, valid = jax.vmap(
            lambda r: agg_lib.refinement_indices(agg, r, refine_budget)
        )(rankings)                                          # [Q, B] x2
        covered = jax.vmap(
            lambda r: agg_lib.buckets_fully_covered(agg, r, refine_budget)
        )(rankings)                                          # [Q, K]
        covered = covered & (agg.counts[None, :] > 0)

    # Gather-free exact distances: each selected original is read straight
    # from HBM by the row-walk kernel ([Q,B,D] never materializes).
    with jax.named_scope("stage2.rows"):
        d_ref = kernel_ops.refine_distances(test_x, train_x, idx, valid)
        ref_y = train_y[idx]                                 # [Q, B] ints

    # Fused finalize: masked centroids seed the running k-best, refined
    # candidates merge into the same scratch (replaces concatenate+top_k).
    with jax.named_scope("stage2.merge"):
        d_cent_masked = jnp.where(covered, BIG, d_cent)
        if not with_bound:
            best_d, best_l = kernel_ops.candidate_topk(
                d_cent_masked,
                jnp.broadcast_to(
                    knn_agg.bucket_labels[None, :], d_cent.shape
                ),
                k=k,
            )
            return kernel_ops.candidate_topk(
                d_ref, ref_y, best_d, best_l, k=k
            )

        best_d, best_c = kernel_ops.candidate_topk(
            d_cent_masked,
            jnp.broadcast_to(cent_comb[None, :], d_cent.shape),
            k=kk,
        )
        ref_comb = ref_y * jnp.int32(n_k + 1) + jnp.int32(n_k)
        d, comb = kernel_ops.candidate_topk(
            d_ref, ref_comb, best_d, best_c, k=kk
        )
        bid = comb % jnp.int32(n_k + 1)
        labels = comb // jnp.int32(n_k + 1)
        hidden = _hidden_risk(
            d_cent_masked, knn_agg.spread, bid, d[:, k], n_k
        )
        bound = _vote_bound(
            d, labels, spread_ext[bid], disp_ext[bid], k, hidden=hidden
        )
        return d[:, :k], labels[:, :k], bound


# ---------------------------------------------------------------------------
# end-to-end jobs (single-host reference path used by tests/benchmarks;
# the pod-mesh path shards train_x/train_y over the `data` axis with the
# identical map/combine functions via core.engine.MapReduce)
# ---------------------------------------------------------------------------

def run_exact(
    train_x, train_y, test_x, *, k: int, n_classes: int, n_shards: int = 1
):
    shards_d, shards_l = [], []
    for s in range(n_shards):
        sl = slice(
            s * train_x.shape[0] // n_shards,
            (s + 1) * train_x.shape[0] // n_shards,
        )
        d, l = exact_map(train_x[sl], train_y[sl], test_x, k=k)
        shards_d.append(d)
        shards_l.append(l)
    d, l = merge_topk(jnp.stack(shards_d), jnp.stack(shards_l), k)
    return majority_vote(d, l, n_classes)


def run_accurateml(
    train_x, train_y, test_x, *, k: int, n_classes: int,
    compression_ratio: float, eps_max: float, lsh_key: jax.Array,
    n_shards: int = 1, n_hashes: int = 4, bucket_width: float = 4.0,
):
    shards_d, shards_l = [], []
    n = train_x.shape[0]
    for s in range(n_shards):
        sl = slice(s * n // n_shards, (s + 1) * n // n_shards)
        sx, sy = train_x[sl], train_y[sl]
        cfg = lsh_lib.config_for_compression(
            sx.shape[0], compression_ratio, n_hashes=n_hashes,
            bucket_width=bucket_width,
        )
        params = lsh_lib.init_lsh(
            jax.random.fold_in(lsh_key, s), sx.shape[1], cfg
        )
        knn_agg = build_knn_aggregates(sx, sy, params, n_classes)
        budget = refine_lib.eps_to_budget(sx.shape[0], eps_max)
        d, l = accurateml_map(
            sx, sy, knn_agg, test_x, k=k, refine_budget=budget
        )
        shards_d.append(d)
        shards_l.append(l)
    d, l = merge_topk(jnp.stack(shards_d), jnp.stack(shards_l), k)
    return majority_vote(d, l, n_classes)


def run_sampled(
    train_x, train_y, test_x, *, k: int, n_classes: int,
    sample_frac: float, sample_key: jax.Array, n_shards: int = 1,
):
    shards_d, shards_l = [], []
    n = train_x.shape[0]
    for s in range(n_shards):
        sl = slice(s * n // n_shards, (s + 1) * n // n_shards)
        sx, sy = train_x[sl], train_y[sl]
        ns = max(1, int(sample_frac * sx.shape[0]))
        perm = jax.random.permutation(
            jax.random.fold_in(sample_key, s), sx.shape[0]
        )
        d, l = sampled_map(sx, sy, test_x, perm, k=k, n_sample=ns)
        shards_d.append(d)
        shards_l.append(l)
    d, l = merge_topk(jnp.stack(shards_d), jnp.stack(shards_l), k)
    return majority_vote(d, l, n_classes)


# ---------------------------------------------------------------------------
# serving adapter (repro.serve.Servable)
# ---------------------------------------------------------------------------

class KNNServable(serve_servable.LSHServableBase):
    """kNN classification behind the ``repro.serve.Servable`` protocol.

    One instance holds one training shard.  ``build`` produces the cacheable
    aggregates for a compression ratio; ``run`` executes ``accurateml_map``
    through the MapReduce engine (all_gather combine: merge shard top-k,
    majority-vote), so ``last_shuffle_bytes`` is metered on the serving path.
    Request payload: ``(query_vector [D],)``; answer: predicted class (int).
    """

    name = "knn"

    def __init__(
        self,
        train_x: jax.Array,
        train_y: jax.Array,
        *,
        n_classes: int,
        k: int = 5,
        lsh_key: jax.Array,
        n_hashes: int = 4,
        bucket_width: float = 4.0,
        engine: engine_lib.MapReduce | None = None,
        store=None,
        pyramid_spec=None,
    ):
        super().__init__(
            (train_x, train_y), lsh_key=lsh_key, n_hashes=n_hashes,
            bucket_width=bucket_width, engine=engine, store=store,
            pyramid_spec=pyramid_spec,
        )
        self.train_x = train_x
        # The table as stage 2's row walk reads it, laid out once here
        # rather than on every refined batch.
        self.train_rows = row_table(train_x)
        self.train_y = train_y
        self.n_classes = n_classes
        self.k = k

    # --- repro.store pyramid hooks ---
    def hash_features(self) -> jax.Array:
        return self.train_x

    def mergeable_stats(self, fine_ids, n_buckets):
        return knn_mergeable_stats(
            self.train_x, self.train_y, fine_ids, n_buckets, self.n_classes
        )

    def assemble(self, stats, index) -> KNNAggregates:
        prepared = knn_assemble(stats, index)
        means = prepared.agg.means.astype(self.train_x.dtype)
        return KNNAggregates(
            agg=dataclasses.replace(prepared.agg, means=means),
            bucket_labels=prepared.bucket_labels,
            spread=prepared.spread,
            dispersion=prepared.dispersion,
        )

    def probe_payload(self) -> tuple:
        return (self.train_x[0],)

    def pad_batch(self, payloads, batch: int) -> tuple:
        return self.stack_pad(payloads, batch)

    def run(
        self, prepared: KNNAggregates, batch_payload: tuple,
        *, refine_budget: int,
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        (test_x,) = batch_payload

        def reduce_fn(g):
            # Keep the merged top-k (distances, labels) next to the vote:
            # the vote is the answer, the neighbour sets feed the stage-1 vs
            # refined accuracy proxy (top-k label-overlap divergence).  The
            # per-query bound merges via max across shards — the claim must
            # hold for every shard's contribution to the merged answer.
            d, l = merge_topk(g[0], g[1], self.k)
            return d, l, majority_vote(d, l, self.n_classes), jnp.max(
                g[2], axis=0
            )

        map_fn = partial(
            accurateml_map, k=self.k, refine_budget=refine_budget,
            with_bound=True,
        )
        combine = engine_lib.CombineSpec(
            mode="all_gather", reduce_fn=reduce_fn,
        )
        return self.engine.run(
            map_fn, combine, self.train_rows, self.train_y,
            replicated_args=(prepared, test_x),
        )

    def unpack(self, outputs: tuple, n: int) -> list:
        return [int(v) for v in np.asarray(outputs[2][:n])]

    def error_bounds(self, stage1_out, n: int) -> list:
        """Per-query claimed bound on label divergence of the stage-1 vote."""
        bounds = np.asarray(stage1_out[3][:n])
        return [
            ErrorBound(
                value=float(b),
                metric="label_divergence",
                confidence=KNN_BOUND_CONFIDENCE,
            )
            for b in bounds
        ]

    def accuracy_proxy(self, stage1_out, refined_out, n: int) -> list[float]:
        """1 - (top-k label multiset overlap / k) per query.

        0.0 = refinement kept the same neighbour-label multiset; 1.0 = it
        replaced every neighbour.  Padding rows (distance >= BIG/2) are
        excluded from both sides; the denominator stays k so lost
        neighbours also count as divergence.
        """
        import collections

        d1, l1 = np.asarray(stage1_out[0][:n]), np.asarray(stage1_out[1][:n])
        d2, l2 = np.asarray(refined_out[0][:n]), np.asarray(refined_out[1][:n])
        out = []
        for i in range(n):
            c1 = collections.Counter(l1[i][d1[i] < BIG / 2].tolist())
            c2 = collections.Counter(l2[i][d2[i] < BIG / 2].tolist())
            overlap = sum((c1 & c2).values())
            out.append(1.0 - overlap / self.k)
        return out


def accuracy(pred: jax.Array, truth: jax.Array) -> float:
    return float(jnp.mean((pred == truth).astype(jnp.float32)))


def accuracy_loss(acc_exact: float, acc_approx: float) -> float:
    """Paper metric: decreased accuracy / exact accuracy."""
    return max(0.0, (acc_exact - acc_approx) / max(acc_exact, 1e-12))
