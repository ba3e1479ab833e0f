"""Per-kernel validation: Pallas bodies (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.aggregated_attention import aggregated_attention_pallas
from repro.kernels.cf_refine import cf_refine_pallas
from repro.kernels.cf_weights import cf_weights_pallas
from repro.kernels.distance_topk import distance_topk_pallas
from repro.kernels.knn_distance import knn_distance_pallas
from repro.kernels.lsh_hash import lsh_hash_pallas
from repro.kernels.refine_distances import refine_distances_pallas
from repro.kernels.topk_stream import BIG, candidate_topk_pallas


@pytest.mark.parametrize("q,n,d", [
    (8, 16, 7), (100, 130, 32), (128, 128, 217), (65, 257, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_knn_distance_kernel(q, n, d, dtype):
    key = jax.random.PRNGKey(q * 1000 + n)
    qs = jax.random.normal(key, (q, d), dtype)
    ps = jax.random.normal(jax.random.fold_in(key, 1), (n, d), dtype)
    got = knn_distance_pallas(qs, ps, tq=64, tn=64, interpret=True)
    want = ref.knn_distance(qs, ps)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d,h", [(64, 16, 4), (200, 217, 6), (33, 8, 1)])
def test_lsh_hash_kernel(n, d, h):
    key = jax.random.PRNGKey(n)
    x = jax.random.normal(key, (n, d))
    a = jax.random.normal(jax.random.fold_in(key, 1), (d, h))
    b = jax.random.uniform(jax.random.fold_in(key, 2), (h,), maxval=4.0)
    got = lsh_hash_pallas(x, a, b, 4.0, tn=64, interpret=True)
    want = ref.lsh_hash(x, a, b, 4.0)
    # floor() at float boundaries: allow off-by-one on <0.1% of entries
    diff = np.abs(np.asarray(got) - np.asarray(want))
    assert (diff > 0).mean() < 1e-3
    assert diff.max() <= 1


@pytest.mark.parametrize("qn,un,i", [(16, 32, 20), (64, 130, 64), (5, 7, 300)])
def test_cf_weights_kernel(qn, un, i):
    key = jax.random.PRNGKey(qn)
    r = jax.random.randint(key, (qn + un, i), 0, 6).astype(jnp.float32)
    m = (jax.random.uniform(jax.random.fold_in(key, 1), (qn + un, i)) < 0.3
         ).astype(jnp.float32)
    a, am = (r * m)[:qn], m[:qn]
    u, um = (r * m)[qn:], m[qn:]
    got = cf_weights_pallas(a, am, u, um, tq=64, tu=64, interpret=True)
    want = ref.cf_weights(a, am, u, um)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _agg_case(key, s, kb, hq, hkv, dk, dv, refine_frac=0.4, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (hq, dk), dtype)
    k_cache = jax.random.normal(ks[1], (s, hkv, dk), dtype)
    v_cache = jax.random.normal(ks[2], (s, hkv, dv), dtype)
    bucket_of = jax.random.randint(ks[3], (s,), 0, kb)
    counts = jax.ops.segment_sum(
        jnp.ones((s,), jnp.int32), bucket_of, num_segments=kb
    )
    # centroids = true bucket means (as the cache builder produces)
    mean_k = jax.vmap(
        lambda h: jax.ops.segment_sum(
            k_cache[:, h, :].astype(jnp.float32), bucket_of,
            num_segments=kb,
        ), in_axes=0, out_axes=1,
    )(jnp.arange(hkv)) / jnp.maximum(counts[:, None, None], 1)
    mean_v = jax.vmap(
        lambda h: jax.ops.segment_sum(
            v_cache[:, h, :].astype(jnp.float32), bucket_of,
            num_segments=kb,
        ), in_axes=0, out_axes=1,
    )(jnp.arange(hkv)) / jnp.maximum(counts[:, None, None], 1)
    n_ref = max(1, int(refine_frac * kb))
    refined = jnp.zeros((kb,), bool).at[:n_ref].set(True) & (counts > 0)
    return q, k_cache, v_cache, bucket_of, mean_k, mean_v, counts, refined


@pytest.mark.parametrize("s,kb,hq,hkv,dk,dv", [
    (64, 8, 4, 2, 16, 16),
    (200, 16, 8, 8, 32, 32),
    (128, 10, 8, 1, 64, 48),   # MQA + dv != dk (MLA latent shape)
])
def test_aggregated_attention_kernel(s, kb, hq, hkv, dk, dv):
    case = _agg_case(jax.random.PRNGKey(s + kb), s, kb, hq, hkv, dk, dv)
    scale = 1.0 / np.sqrt(dk)
    got = aggregated_attention_pallas(
        *case, scale=scale, valid_len=s - 3, tile=64, interpret=True
    )
    want = ref.aggregated_attention_decode(*case, scale, s - 3)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_aggregated_attention_all_refined_equals_exact():
    """refine=all ==> plain masked attention over the cache."""
    s, kb, hq, hkv, dk = 96, 12, 4, 2, 16
    case = list(_agg_case(jax.random.PRNGKey(0), s, kb, hq, hkv, dk, dk))
    counts = case[6]
    case[7] = counts > 0        # all non-empty buckets refined
    scale = 1.0 / np.sqrt(dk)
    got = aggregated_attention_pallas(
        *case, scale=scale, valid_len=s, tile=64, interpret=True
    )
    # plain softmax attention reference
    q, k_cache, v_cache = case[0], case[1], case[2]
    group = hq // hkv
    outs = []
    for h in range(hq):
        kvh = h // group
        logits = (k_cache[:, kvh, :] @ q[h]) * scale
        p = jax.nn.softmax(logits)
        outs.append(p @ v_cache[:, kvh, :])
    want = jnp.stack(outs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_aggregated_attention_quality_clustered():
    """With clustered keys, partial refinement tracks exact attention
    closely (the paper's small-accuracy-loss regime)."""
    s, kb, hq, hkv, dk = 256, 32, 4, 2, 32
    key = jax.random.PRNGKey(7)
    centers = jax.random.normal(key, (kb, hkv, dk)) * 3.0
    assign = jax.random.randint(jax.random.fold_in(key, 1), (s,), 0, kb)
    k_cache = centers[assign] + 0.1 * jax.random.normal(
        jax.random.fold_in(key, 2), (s, hkv, dk)
    )
    v_cache = jax.random.normal(jax.random.fold_in(key, 3), (s, hkv, dk))
    q = centers[3].reshape(hkv, 1, dk).repeat(hq // hkv, 1).reshape(hq, dk)
    counts = jax.ops.segment_sum(
        jnp.ones((s,), jnp.int32), assign, num_segments=kb
    )
    mean_k = jax.vmap(
        lambda h: jax.ops.segment_sum(
            k_cache[:, h, :], assign, num_segments=kb
        ), in_axes=0, out_axes=1,
    )(jnp.arange(hkv)) / jnp.maximum(counts[:, None, None], 1)
    mean_v = jax.vmap(
        lambda h: jax.ops.segment_sum(
            v_cache[:, h, :], assign, num_segments=kb
        ), in_axes=0, out_axes=1,
    )(jnp.arange(hkv)) / jnp.maximum(counts[:, None, None], 1)

    scale = 1.0 / np.sqrt(dk)
    # correlation-ranked refinement (stage 1 of Algorithm 1)
    corr = jnp.max(
        jnp.einsum("hd,Kd->hK", q.reshape(hq, dk)[:hkv], mean_k[:, 0]), 0
    )
    _, top = jax.lax.top_k(jnp.where(counts > 0, corr, -jnp.inf), 4)
    refined = jnp.zeros((kb,), bool).at[top].set(True)

    approx = ref.aggregated_attention_decode(
        q, k_cache, v_cache, assign, mean_k, mean_v, counts, refined,
        scale, s,
    )
    exact = ref.aggregated_attention_decode(
        q, k_cache, v_cache, assign, mean_k, mean_v, counts, counts > 0,
        scale, s,
    )
    cos = jnp.sum(approx * exact, -1) / (
        jnp.linalg.norm(approx, axis=-1) * jnp.linalg.norm(exact, axis=-1)
    )
    assert float(jnp.min(cos)) > 0.98, np.asarray(cos)


# ---------------------------------------------------------------------------
# fused two-stage hot path: streaming distance+top-k + gather-free refine
# ---------------------------------------------------------------------------

def _topk_case(seed, q, n, d, valid_frac=0.8):
    key = jax.random.PRNGKey(seed)
    qs = jax.random.normal(key, (q, d))
    ps = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    labs = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, 11)
    valid = jax.random.uniform(jax.random.fold_in(key, 3), (n,)) < valid_frac
    return qs, ps, labs, valid


@settings(max_examples=12, deadline=None)
@given(
    q=st.integers(min_value=1, max_value=70),
    n=st.integers(min_value=1, max_value=300),
    d=st.integers(min_value=1, max_value=140),
    k=st.integers(min_value=1, max_value=8),
)
def test_distance_topk_property(q, n, d, k):
    """Interpret-mode kernel == oracle over arbitrary (non-tile-multiple)
    Q/N/D/k, including n < k (selection pads with BIG)."""
    qs, ps, labs, valid = _topk_case(q * 7919 + n * 31 + d, q, n, d)
    got_d, got_l = distance_topk_pallas(
        qs, ps, labs, valid, k=k, tq=64, tn=64, interpret=True
    )
    want_d, want_l = ref.distance_topk(qs, ps, labs, valid, k=k)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-4, atol=1e-4)
    real = np.asarray(want_d) < float(BIG) / 2  # label ties only matter on
    np.testing.assert_array_equal(             # real (finite) selections
        np.asarray(got_l)[real], np.asarray(want_l)[real]
    )


def test_distance_topk_padding_never_selected():
    """BIG sentinel, not zero padding: a masked-out point *identical to the
    query* (squared distance exactly 0 — the best possible candidate under
    zero padding) must never enter the top-k."""
    key = jax.random.PRNGKey(5)
    qs = jax.random.normal(key, (6, 10))
    far = jax.random.normal(jax.random.fold_in(key, 1), (50, 10)) + 30.0
    pts = jnp.concatenate([far, qs], axis=0)     # last 6 rows: exact copies
    labs = jnp.concatenate([jnp.zeros((50,), jnp.int32),
                            jnp.ones((6,), jnp.int32)])
    valid = jnp.concatenate([jnp.ones((50,), bool), jnp.zeros((6,), bool)])
    got_d, got_l = distance_topk_pallas(
        qs, pts, labs, valid, k=4, tq=64, tn=64, interpret=True
    )
    assert (np.asarray(got_l) == 0).all()        # only far (valid) points
    assert (np.asarray(got_d) > 1.0).all()


def test_distance_topk_all_padding():
    """Every point masked out -> all selections are the BIG sentinel (the
    all-empty-buckets stage-1 case); majority_vote treats them as invalid."""
    qs, ps, labs, _ = _topk_case(3, 5, 40, 12)
    none = jnp.zeros((40,), bool)
    got_d, got_l = distance_topk_pallas(
        qs, ps, labs, none, k=3, tq=64, tn=64, interpret=True
    )
    assert (np.asarray(got_d) >= float(BIG) / 2).all()
    want_d, _ = ref.distance_topk(qs, ps, labs, none, k=3)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d))


@settings(max_examples=10, deadline=None)
@given(
    q=st.integers(min_value=1, max_value=40),
    m=st.integers(min_value=1, max_value=200),
    k=st.integers(min_value=1, max_value=8),
)
def test_candidate_topk_seeded_property(q, m, k):
    """Seeded streaming selection == one top_k over the concatenation."""
    key = jax.random.PRNGKey(q * 1009 + m)
    d = jax.random.uniform(key, (q, m)) * 10.0
    d = jnp.where(jax.random.uniform(jax.random.fold_in(key, 1), (q, m)) < 0.9,
                  d, BIG)                        # some pre-masked candidates
    lab = jax.random.randint(jax.random.fold_in(key, 2), (q, m), 0, 7)
    init_d = jnp.sort(jax.random.uniform(jax.random.fold_in(key, 3),
                                         (q, k)) * 10.0, axis=1)
    init_l = jax.random.randint(jax.random.fold_in(key, 4), (q, k), 0, 7)
    got_d, got_l = candidate_topk_pallas(
        d, lab, init_d, init_l, k=k, tq=64, tc=64, interpret=True
    )
    want_d, want_l = ref.candidate_topk(d, lab, init_d, init_l, k=k)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-6, atol=1e-6)
    real = np.asarray(want_d) < float(BIG) / 2
    np.testing.assert_array_equal(
        np.asarray(got_l)[real], np.asarray(want_l)[real]
    )


@settings(max_examples=10, deadline=None)
@given(
    q=st.integers(min_value=1, max_value=30),
    n=st.integers(min_value=1, max_value=120),
    d=st.integers(min_value=1, max_value=200),
    b=st.integers(min_value=1, max_value=40),
)
def test_refine_distances_property(q, n, d, b):
    """Scalar-prefetch gather-free distances == gathered-einsum oracle,
    including all-padding selections (valid everywhere False)."""
    key = jax.random.PRNGKey(q + n * 13 + d * 101 + b)
    qs = jax.random.normal(key, (q, d))
    xs = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (q, b), 0, n)
    valid = jax.random.uniform(jax.random.fold_in(key, 3), (q, b)) < 0.7
    got = refine_distances_pallas(qs, xs, idx, valid, interpret=True)
    want = ref.refine_distances(qs, xs, idx, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # all-padding bucket: every slot masked -> pure BIG row
    none = jnp.zeros_like(valid)
    got0 = refine_distances_pallas(qs, xs, idx, none, interpret=True)
    assert (np.asarray(got0) >= float(BIG) / 2).all()


@pytest.mark.parametrize("qn,un,ni,b", [(4, 30, 25, 7), (9, 64, 130, 17)])
def test_cf_refine_kernel(qn, un, ni, b):
    key = jax.random.PRNGKey(qn * 100 + b)
    r = jax.random.randint(key, (qn + un, ni), 0, 6).astype(jnp.float32)
    m = (jax.random.uniform(jax.random.fold_in(key, 1), (qn + un, ni)) < 0.3
         ).astype(jnp.float32)
    a, am = (r * m)[:qn], m[:qn]
    u, um = (r * m)[qn:], m[qn:]
    idx = jax.random.randint(jax.random.fold_in(key, 2), (qn, b), 0, un)
    use = jax.random.uniform(jax.random.fold_in(key, 3), (qn, b)) < 0.6
    got = cf_refine_pallas(a, am, u, um, idx, use, shrink=8.0,
                           interpret=True)
    want = ref.cf_refine(a, am, u, um, idx, use, shrink=8.0)
    for g, w, name in zip(got, want, ("w_ref", "num_delta", "den_delta")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_refine_kernels_chunked_walk_equals_one_pass(monkeypatch):
    """A selection too large for SMEM is walked in chunks; the chunked walk
    returns exactly what one pass does (CF sums add in the same order)."""
    from repro.kernels import refine_distances as rd

    key = jax.random.PRNGKey(21)
    xs = jax.random.normal(key, (50, 10))
    qs = jax.random.normal(jax.random.fold_in(key, 1), (3, 10))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (3, 17), 0, 50)
    valid = jax.random.uniform(jax.random.fold_in(key, 3), (3, 17)) < 0.7
    r = jax.random.randint(key, (20, 15), 0, 6).astype(jnp.float32)
    m = (jax.random.uniform(jax.random.fold_in(key, 4), (20, 15)) < 0.4
         ).astype(jnp.float32)
    cf_args = ((r * m)[:3], m[:3], (r * m)[3:], m[3:], idx % 17, valid)

    # Unjitted wrappers: each call traces afresh with the current chunk size.
    dist = refine_distances_pallas.__wrapped__
    cf = cf_refine_pallas.__wrapped__
    one_d = dist(qs, xs, idx, valid, interpret=True)
    one_cf = cf(*cf_args, shrink=8.0, interpret=True)
    monkeypatch.setattr(rd, "SMEM_PAIRS", 6)
    assert rd.chunk_selection(3, 17) == (2, 9)
    np.testing.assert_array_equal(
        np.asarray(dist(qs, xs, idx, valid, interpret=True)),
        np.asarray(one_d),
    )
    for got, want in zip(cf(*cf_args, shrink=8.0, interpret=True), one_cf):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cf_refine_all_padding_is_zero():
    """No used candidate -> zero weights and zero contribution (not NaN)."""
    key = jax.random.PRNGKey(11)
    r = jax.random.randint(key, (20, 15), 0, 6).astype(jnp.float32)
    m = (jax.random.uniform(jax.random.fold_in(key, 1), (20, 15)) < 0.4
         ).astype(jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 2), (3, 5), 0, 15)
    use = jnp.zeros((3, 5), bool)
    w, num, den = cf_refine_pallas(
        (r * m)[:3], m[:3], (r * m)[5:], m[5:], idx, use, shrink=8.0,
        interpret=True,
    )
    assert np.isfinite(np.asarray(w)).all()
    assert (np.asarray(w) == 0).all()
    assert (np.asarray(num) == 0).all() and (np.asarray(den) == 0).all()


def test_topk_fewer_candidates_than_k():
    """n < k: both oracle and kernel pad the selection with BIG instead of
    raising (lax.top_k alone would)."""
    qs, ps, labs, _ = _topk_case(1, 4, 3, 9)
    got_d, got_l = distance_topk_pallas(
        qs, ps, labs, None, k=5, tq=64, tn=64, interpret=True
    )
    want_d, want_l = ref.distance_topk(qs, ps, labs, None, k=5)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(want_d)[:, 3:] >= float(BIG) / 2).all()
    real = np.asarray(want_d) < float(BIG) / 2
    np.testing.assert_array_equal(
        np.asarray(got_l)[real], np.asarray(want_l)[real]
    )
    # unseeded candidate selection over a too-narrow candidate set
    cd = jnp.asarray([[1.0, 2.0]])
    cl = jnp.asarray([[4, 6]], dtype=jnp.int32)
    d2, l2 = ref.candidate_topk(cd, cl, k=4)
    np.testing.assert_allclose(np.asarray(d2)[0, :2], [1.0, 2.0])
    assert (np.asarray(d2)[0, 2:] >= float(BIG) / 2).all()
