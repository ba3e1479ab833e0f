"""Tests for information aggregation + the refinement index machinery."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import aggregate as agg_lib
from repro.core import correlation as corr_lib


def _random_case(seed, n=200, d=8, k=16):
    key = jax.random.PRNGKey(seed)
    data = jax.random.normal(key, (n, d))
    ids = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, k)
    return data, ids, k


def test_segment_means_match_numpy():
    data, ids, k = _random_case(0)
    agg = agg_lib.aggregate_by_bucket(data, ids, k)
    dn, idn = np.asarray(data), np.asarray(ids)
    for b in range(k):
        pts = dn[idn == b]
        if len(pts):
            np.testing.assert_allclose(
                np.asarray(agg.means[b]), pts.mean(0), rtol=1e-5, atol=1e-5
            )
            assert int(agg.counts[b]) == len(pts)
        else:
            assert int(agg.counts[b]) == 0


def test_index_consistency():
    """perm groups points contiguously by bucket; offsets delimit buckets."""
    data, ids, k = _random_case(3)
    agg = agg_lib.aggregate_by_bucket(data, ids, k)
    idn = np.asarray(ids)
    perm = np.asarray(agg.perm)
    off = np.asarray(agg.offsets)
    assert off[0] == 0 and off[-1] == len(idn)
    for b in range(k):
        seg = perm[off[b]:off[b + 1]]
        assert (idn[seg] == b).all()
    # every original point appears exactly once
    assert sorted(perm.tolist()) == list(range(len(idn)))


def test_counts_sum_to_n():
    data, ids, k = _random_case(7, n=333, k=29)
    agg = agg_lib.aggregate_by_bucket(data, ids, k)
    assert int(agg.counts.sum()) == 333


def test_bucket_sumsq_matches_numpy():
    data, ids, k = _random_case(19)
    ss = agg_lib.bucket_sumsq(data, ids, k)
    dn, idn = np.asarray(data), np.asarray(ids)
    for b in range(k):
        np.testing.assert_allclose(
            np.asarray(ss[b]), (dn[idn == b] ** 2).sum(0),
            rtol=1e-5, atol=1e-5,
        )


def test_empty_bucket_uncertainty_is_infinite():
    """Empty buckets report +inf spread/dispersion — never 0 or NaN.  A
    zero claim from a bucket with no members would let an error bound
    assert certainty about unknown content; pinned alongside the
    BIG-sentinel masking of empty centroids in the distance kernels."""
    data = jnp.asarray(np.random.RandomState(0).randn(20, 4), jnp.float32)
    ids = jnp.zeros((20,), jnp.int32)              # only bucket 0 populated
    k = 4
    counts = jax.ops.segment_sum(
        jnp.ones((20,), jnp.int32), ids, num_segments=k
    )
    sums = jax.ops.segment_sum(data, ids, num_segments=k)
    sumsq = agg_lib.bucket_sumsq(data, ids, k)
    spread = np.asarray(agg_lib.bucket_spread(sums, sumsq, counts))
    assert np.isfinite(spread[0]) and spread[0] > 0
    assert np.isinf(spread[1:]).all()
    assert not np.isnan(spread).any()

    hist = jnp.zeros((k, 3)).at[0, 1].set(5.0)     # pure bucket 0, rest empty
    disp = np.asarray(agg_lib.histogram_dispersion(hist))
    assert disp[0] == 0.0                          # label-pure: certain
    assert np.isinf(disp[1:]).all()
    assert not np.isnan(disp).any()


def test_centered_second_moment_clamps_negative_noise():
    """fp cancellation (s2 slightly under s²/c) must clip to 0, and c == 0
    cells yield 0 mass — the bucket-level empty contract lives in
    bucket_spread/histogram_dispersion, not here."""
    s = jnp.asarray([[3.0], [0.0]])
    s2 = jnp.asarray([[2.9], [0.0]])               # < 3²/3 = 3.0
    c = jnp.asarray([[3.0], [0.0]])
    cv = np.asarray(agg_lib.centered_second_moment(s, s2, c))
    assert (cv >= 0).all() and cv[1, 0] == 0.0


def test_refinement_indices_walk_ranked_buckets():
    data, ids, k = _random_case(11, n=100, k=10)
    agg = agg_lib.aggregate_by_bucket(data, ids, k)
    corr = jnp.arange(k, dtype=jnp.float32)  # bucket k-1 most correlated
    ranking = corr_lib.rank_buckets(corr, agg.counts)
    budget = 30
    idx, valid = agg_lib.refinement_indices(agg, ranking, budget)
    assert idx.shape == (budget,)
    picked_buckets = np.asarray(ids)[np.asarray(idx)][np.asarray(valid)]
    # first selected points must come from the top-ranked non-empty bucket
    ranked = [int(b) for b in np.asarray(ranking)]
    counts = np.asarray(agg.counts)
    first_nonempty = next(b for b in ranked if counts[b] > 0)
    assert picked_buckets[0] == first_nonempty
    # selections follow ranking order (non-interleaved buckets)
    seen = []
    for b in picked_buckets:
        if not seen or seen[-1] != b:
            seen.append(int(b))
    order = {b: i for i, b in enumerate(ranked)}
    assert all(
        order[seen[i]] < order[seen[i + 1]] for i in range(len(seen) - 1)
    )


def test_budget_larger_than_n_pads():
    data, ids, k = _random_case(13, n=50, k=5)
    agg = agg_lib.aggregate_by_bucket(data, ids, k)
    ranking = corr_lib.rank_buckets(jnp.zeros(k), agg.counts)
    idx, valid = agg_lib.refinement_indices(agg, ranking, 80)
    assert int(valid.sum()) == 50
    chosen = np.sort(np.asarray(idx)[np.asarray(valid)])
    np.testing.assert_array_equal(chosen, np.arange(50))


def test_buckets_fully_covered():
    data, ids, k = _random_case(17, n=60, k=6)
    agg = agg_lib.aggregate_by_bucket(data, ids, k)
    corr = jnp.arange(k, dtype=jnp.float32)
    ranking = corr_lib.rank_buckets(corr, agg.counts)
    counts = np.asarray(agg.counts)
    ranked = np.asarray(ranking)
    budget = int(counts[ranked[0]] + counts[ranked[1]])  # exactly 2 buckets
    covered = np.asarray(
        agg_lib.buckets_fully_covered(agg, ranking, budget)
    )
    assert covered[ranked[0]] and covered[ranked[1]]
    if k > 2 and counts[ranked[2]] > 0:
        assert not covered[ranked[2]]


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1000),
    n=st.integers(min_value=5, max_value=120),
    k=st.integers(min_value=1, max_value=20),
    budget=st.integers(min_value=0, max_value=150),
)
def test_refinement_indices_properties(seed, n, k, budget):
    data = jax.random.normal(jax.random.PRNGKey(seed), (n, 4))
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (n,), 0, k)
    agg = agg_lib.aggregate_by_bucket(data, ids, k)
    corr = jax.random.normal(jax.random.PRNGKey(seed + 2), (k,))
    ranking = corr_lib.rank_buckets(corr, agg.counts)
    if budget == 0:
        return
    idx, valid = agg_lib.refinement_indices(agg, ranking, budget)
    v = np.asarray(valid)
    assert v.sum() == min(budget, n)
    chosen = np.asarray(idx)[v]
    assert len(set(chosen.tolist())) == len(chosen)  # no duplicates
