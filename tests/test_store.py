"""Tests for repro.store: multi-resolution pyramid exactness, nested LSH
ids, streaming ingest, and snapshot/restore persistence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.apps.cf import CFServable
from repro.apps.knn import KNNServable
from repro.core import aggregate as agg_lib
from repro.core import lsh as lsh_lib
from repro.store import (
    AggregateStore, PyramidSpec, SOURCE_BUILT, SOURCE_MEMORY, SOURCE_MERGED,
    SOURCE_RESTORED, StreamingAggregate,
)

N, D, C = 384, 8, 5


@pytest.fixture(scope="module")
def knn_pair():
    """Two independent servables over identical data + LSH key: one builds
    each ratio cold, the other reuses its pyramid."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (N, D))
    y = jax.random.randint(jax.random.fold_in(key, 1), (N,), 0, C)

    def make():
        return KNNServable(x, y, n_classes=C, k=3,
                           lsh_key=jax.random.PRNGKey(7))

    return make


# ---------------------------------------------------------------------------
# pyramid spec / quantization
# ---------------------------------------------------------------------------

def test_spec_grid_shape():
    spec = PyramidSpec.for_points(16_384, branch=2, finest_ratio=4.0)
    assert spec.base_buckets == 4096
    assert spec.n_buckets(0) == 4096 and spec.n_buckets(2) == 1024
    assert spec.ratio(0) == 4.0
    # Levels halve buckets; ratios double.
    for lvl in range(spec.n_levels - 1):
        assert spec.n_buckets(lvl) == 2 * spec.n_buckets(lvl + 1)


def test_spec_ratio_quantization_is_drift_proof():
    spec = PyramidSpec.for_points(10_000)
    base = spec.quantize_ratio(20.0)
    for drift in (1e-9, -1e-9, 1e-7):
        assert spec.quantize_ratio(20.0 * (1 + drift)) == base
    # Monotone: a much coarser request lands on a coarser level.
    assert spec.quantize_ratio(200.0) > spec.quantize_ratio(10.0)


def test_spec_clamps_out_of_range_ratios():
    spec = PyramidSpec.for_points(1000, finest_ratio=4.0)
    assert spec.level_for_ratio(0.001) == 0
    assert spec.level_for_ratio(1e12) == spec.n_levels - 1


# ---------------------------------------------------------------------------
# nested LSH ids
# ---------------------------------------------------------------------------

def test_nested_ids_are_prefix_merges():
    """Every coarse id must equal fine_id // factor — the exactness
    precondition of the whole pyramid."""
    key = jax.random.PRNGKey(3)
    data = jax.random.normal(key, (200, D))
    for k_coarse in (32, 16, 4):
        cfg = lsh_lib.nested_config(64, k_coarse)
        params = lsh_lib.init_lsh(jax.random.PRNGKey(9), D, cfg)
        fine = np.asarray(lsh_lib.fine_bucket_ids(data, params))
        coarse = np.asarray(lsh_lib.bucket_ids(data, params))
        np.testing.assert_array_equal(coarse, fine // (64 // k_coarse))
        assert coarse.min() >= 0 and coarse.max() < k_coarse


def test_nested_config_validation():
    with pytest.raises(ValueError):
        lsh_lib.LSHConfig(n_buckets=48, base_buckets=64)  # not a divisor
    with pytest.raises(ValueError):
        lsh_lib.LSHConfig(n_buckets=128, base_buckets=64)  # coarser base


def test_nested_build_matches_flat_semantics():
    """aggregate_nested must agree with a direct aggregate_by_bucket over
    the coarse ids (same buckets, same members; means to fp tolerance)."""
    key = jax.random.PRNGKey(4)
    data = jax.random.normal(key, (300, D))
    cfg = lsh_lib.nested_config(64, 16)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(2), D, cfg)
    nested = agg_lib.build_aggregates(data, params)
    coarse_ids = lsh_lib.bucket_ids(data, params)
    flat = agg_lib.aggregate_by_bucket(data, coarse_ids, 16)
    np.testing.assert_array_equal(np.asarray(nested.counts),
                                  np.asarray(flat.counts))
    np.testing.assert_allclose(np.asarray(nested.means),
                               np.asarray(flat.means), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(nested.offsets),
                                  np.asarray(flat.offsets))
    np.testing.assert_array_equal(np.asarray(nested.bucket_of),
                                  np.asarray(coarse_ids))
    # Both index the same bucket membership.
    off = np.asarray(nested.offsets)
    perm = np.asarray(nested.perm)
    bo = np.asarray(coarse_ids)
    for b in range(16):
        assert (bo[perm[off[b]:off[b + 1]]] == b).all()


# ---------------------------------------------------------------------------
# coarsening exactness (the tentpole acceptance)
# ---------------------------------------------------------------------------

def test_coarsen_bit_identical_to_cold_build(knn_pair):
    """Merging a cached level-0 down to any coarser supported ratio must be
    bit-identical to building that ratio on a cold store."""
    cold = knn_pair()
    warm = knn_pair()
    warm.store.get(warm, warm.pyramid_spec.ratio(0))  # pin the finest level
    for level in range(1, warm.pyramid_spec.n_levels):
        ratio = warm.pyramid_spec.ratio(level)
        built, src_cold = AggregateStore().get(cold, ratio)
        merged, src_warm = warm.store.get(warm, ratio)
        assert src_cold == SOURCE_BUILT and src_warm == SOURCE_MERGED
        np.testing.assert_array_equal(np.asarray(built.agg.counts),
                                      np.asarray(merged.agg.counts))
        np.testing.assert_array_equal(np.asarray(built.agg.means),
                                      np.asarray(merged.agg.means))
        np.testing.assert_array_equal(np.asarray(built.agg.perm),
                                      np.asarray(merged.agg.perm))
        np.testing.assert_array_equal(np.asarray(built.agg.offsets),
                                      np.asarray(merged.agg.offsets))
        np.testing.assert_array_equal(np.asarray(built.bucket_labels),
                                      np.asarray(merged.bucket_labels))


def test_coarsen_bit_identical_for_cf():
    key = jax.random.PRNGKey(5)
    r = jax.random.uniform(key, (128, 24)) * 4 + 1
    m = (jax.random.uniform(jax.random.fold_in(key, 1), (128, 24)) < 0.3
         ).astype(jnp.float32)

    def make():
        return CFServable(r * m, m, lsh_key=jax.random.PRNGKey(8))

    warm = make()
    warm.store.get(warm, warm.pyramid_spec.ratio(0))
    for level in (1, warm.pyramid_spec.n_levels - 1):
        ratio = warm.pyramid_spec.ratio(level)
        built, _ = AggregateStore().get(make(), ratio)
        merged, src = warm.store.get(warm, ratio)
        assert src == SOURCE_MERGED
        for field in ("profile", "profile_mask", "s", "c"):
            np.testing.assert_array_equal(
                np.asarray(getattr(built, field)),
                np.asarray(getattr(merged, field)), err_msg=field,
            )


def test_second_moments_survive_merge_and_snapshot(knn_pair, tmp_path):
    """The sumsq channel is additive like sums/counts: spread and
    dispersion derived from a *merged* or *restored* level must equal the
    cold build's bit-for-bit (the error-bound acceptance for the store)."""
    cold = knn_pair()
    warm = knn_pair()
    warm.store.get(warm, warm.pyramid_spec.ratio(0))
    for level in (1, warm.pyramid_spec.n_levels - 1):
        ratio = warm.pyramid_spec.ratio(level)
        built, _ = AggregateStore().get(cold, ratio)
        merged, src = warm.store.get(warm, ratio)
        assert src == SOURCE_MERGED
        np.testing.assert_array_equal(np.asarray(built.spread),
                                      np.asarray(merged.spread))
        np.testing.assert_array_equal(np.asarray(built.dispersion),
                                      np.asarray(merged.dispersion))
    assert warm.store.save(tmp_path / "snap") == 1
    dst = knn_pair()
    assert dst.store.restore(tmp_path / "snap", [dst]) == 1
    ratio0 = warm.pyramid_spec.ratio(0)
    restored, source = dst.store.get(dst, ratio0)
    assert source == SOURCE_RESTORED
    built0, _ = AggregateStore().get(knn_pair(), ratio0)
    np.testing.assert_array_equal(np.asarray(built0.spread),
                                  np.asarray(restored.spread))
    np.testing.assert_array_equal(np.asarray(built0.dispersion),
                                  np.asarray(restored.dispersion))
    # Populated buckets carry finite spread; only empties are +inf.
    sp = np.asarray(restored.spread)
    counts = np.asarray(restored.agg.counts)
    assert np.isfinite(sp[counts > 0]).all()
    assert np.isinf(sp[counts == 0]).all()


def test_assemble_without_sumsq_degrades_to_infinite_spread():
    """A pre-second-moment snapshot (no 'sumsq' channel) assembles with
    +inf spread everywhere — maximum uncertainty, never a tight claim."""
    from repro.apps.knn import knn_assemble, knn_mergeable_stats

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (N, D))
    y = jax.random.randint(jax.random.fold_in(key, 1), (N,), 0, C)
    cfg = lsh_lib.LSHConfig(n_hashes=4, bucket_width=4.0, n_buckets=32)
    ids = lsh_lib.bucket_ids(x, lsh_lib.init_lsh(jax.random.PRNGKey(7), D, cfg))
    stats = dict(knn_mergeable_stats(x, y, ids, 32, C))
    del stats["sumsq"]
    old = knn_assemble(stats, agg_lib.bucket_index(ids, 32))
    assert np.isinf(np.asarray(old.spread)).all()


def test_store_sources_and_memoization(knn_pair):
    s = knn_pair()
    _, src1 = s.store.get(s, 8.0)
    assert src1 == SOURCE_BUILT
    _, src2 = s.store.get(s, 8.0)
    assert src2 == SOURCE_MEMORY
    _, src3 = s.store.get(s, 32.0)
    assert src3 == SOURCE_MERGED
    _, src4 = s.store.get(s, 32.0)
    assert src4 == SOURCE_MEMORY
    stats = s.store.stats()
    assert stats["builds"] == 1 and stats["merges"] == 1
    assert stats["memory_hits"] == 2 and stats["pyramids"] == 1
    assert stats["resident_bytes"] > 0


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1000),
    n=st.integers(min_value=8, max_value=200),
    levels=st.integers(min_value=1, max_value=4),
)
def test_merge_preserves_counts_and_weighted_means(seed, n, levels):
    """Property: merging pyramid levels preserves total counts and weighted
    means exactly.  Integer-valued features keep every segment sum exactly
    representable in fp32, so 'exactly' means bit-equality, not tolerance."""
    key = jax.random.PRNGKey(seed)
    base = 2 ** (levels + 2)
    data = jax.random.randint(key, (n, 4), -8, 8).astype(jnp.float32)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, base)
    counts = jax.ops.segment_sum(
        jnp.ones((n,), jnp.int32), ids, num_segments=base
    )
    sums = jax.ops.segment_sum(data, ids, num_segments=base)
    factor = 2 ** levels
    counts_m = agg_lib.merge_levels(counts, factor)
    sums_m = agg_lib.merge_levels(sums, factor)
    # Totals preserved exactly.
    assert int(counts_m.sum()) == n
    np.testing.assert_array_equal(
        np.asarray(sums_m.sum(0)), np.asarray(sums.sum(0))
    )
    # Merged stats == direct aggregation over the coarse ids, so the
    # weighted mean (merged_sums / merged_counts) of every coarse bucket is
    # *the* mean of its members — not an approximation of it.
    coarse_ids = ids // factor
    counts_direct = jax.ops.segment_sum(
        jnp.ones((n,), jnp.int32), coarse_ids, num_segments=base // factor
    )
    sums_direct = jax.ops.segment_sum(
        data, coarse_ids, num_segments=base // factor
    )
    np.testing.assert_array_equal(np.asarray(counts_m),
                                  np.asarray(counts_direct))
    np.testing.assert_array_equal(np.asarray(sums_m),
                                  np.asarray(sums_direct))
    means_m = np.asarray(sums_m) / np.maximum(
        np.asarray(counts_m)[:, None], 1
    )
    means_direct = np.asarray(sums_direct) / np.maximum(
        np.asarray(counts_direct)[:, None], 1
    )
    np.testing.assert_array_equal(means_m, means_direct)


def test_coarsen_index_remaps_exactly():
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 16, size=120),
                      jnp.int32)
    index = agg_lib.bucket_index(ids, 16)
    coarse = agg_lib.coarsen_index(index, 4)
    assert coarse.n_buckets == 4
    np.testing.assert_array_equal(np.asarray(coarse.perm),
                                  np.asarray(index.perm))
    np.testing.assert_array_equal(np.asarray(coarse.bucket_of),
                                  np.asarray(ids) // 4)
    off = np.asarray(coarse.offsets)
    perm = np.asarray(coarse.perm)
    bo = np.asarray(ids) // 4
    assert off[0] == 0 and off[-1] == 120
    for b in range(4):
        assert (bo[perm[off[b]:off[b + 1]]] == b).all()


# ---------------------------------------------------------------------------
# streaming ingest
# ---------------------------------------------------------------------------

def _stream(capacity=256, chunk=32, **kw):
    cfg = lsh_lib.LSHConfig(n_hashes=4, bucket_width=4.0, n_buckets=32)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(7), D, cfg)
    return params, StreamingAggregate(
        params, D, capacity=capacity, chunk=chunk, **kw
    )


def test_streaming_append_matches_batch_rebuild():
    """Delta-updated statistics == one-shot segment sums over all rows
    (integer-valued rows so scatter-add order cannot matter)."""
    params, stream = _stream()
    x = jax.random.randint(jax.random.PRNGKey(1), (150, D), -6, 6
                           ).astype(jnp.float32)
    for start, stop in ((0, 60), (60, 110), (110, 150)):
        stream.append(x[start:stop])   # uneven batches, incl. sub-chunk
    assert stream.n == 150
    ids = lsh_lib.fine_bucket_ids(x, params)
    counts_ref = jax.ops.segment_sum(
        jnp.ones((150,), jnp.int32), ids, num_segments=32
    )
    sums_ref = jax.ops.segment_sum(x, ids, num_segments=32)
    live = stream.live_stats()
    np.testing.assert_array_equal(np.asarray(live["counts"]),
                                  np.asarray(counts_ref))
    np.testing.assert_array_equal(np.asarray(live["sums"]),
                                  np.asarray(sums_ref))
    np.testing.assert_array_equal(stream.data(), np.asarray(x))


def test_streaming_extra_stats_and_staleness_schedule():
    _, stream = _stream(extra_shapes={"label_hist": (C,)})
    x = jax.random.normal(jax.random.PRNGKey(2), (120, D))
    oh = np.eye(C, dtype=np.float32)[
        np.random.RandomState(0).randint(0, C, 120)
    ]
    stream.append(x[:80], label_hist=oh[:80])
    assert stream.stale_points == 80 and stream.needs_rebucket
    stats, index, n = stream.level0()          # schedules the rebucket
    assert n == 80 and stream.stale_points == 0
    assert int(stats["label_hist"].sum()) == 80

    stream.append(x[80:90], label_hist=oh[80:90])
    assert stream.stale_points == 10 and not stream.needs_rebucket
    # level0 without a needed rebucket returns the *last* consistent view...
    _, _, n2 = stream.level0()
    assert n2 == 80
    # ...while live statistics already include the new rows.
    assert int(stream.live_stats()["counts"].sum()) == 90
    stream.append(x[90:], label_hist=oh[90:])  # 40 stale > 25% of 80
    assert stream.needs_rebucket
    stats, index, n3 = stream.level0()
    assert n3 == 120 and stream.stale_points == 0


def test_streaming_index_is_consistent_and_adoptable():
    params, stream = _stream()
    x = jax.random.normal(jax.random.PRNGKey(3), (100, D))
    stream.append(x)
    stats, index, n = stream.level0()
    perm, off = np.asarray(index.perm), np.asarray(index.offsets)
    bo = np.asarray(index.bucket_of)
    assert perm.shape == (100,) and off[-1] == 100
    for b in range(32):
        assert (bo[perm[off[b]:off[b + 1]]] == b).all()
    # Adopt into a pyramid and serve from it.
    y = jax.random.randint(jax.random.PRNGKey(4), (100,), 0, C)
    spec = PyramidSpec(n_points=100, base_buckets=32, branch=2, n_levels=4)
    servable = KNNServable(
        jnp.asarray(stream.data()), y, n_classes=C, k=3,
        lsh_key=jax.random.PRNGKey(7), pyramid_spec=spec,
    )
    stats = dict(stats)
    stats["label_hist"] = jax.ops.segment_sum(
        jax.nn.one_hot(y, C), index.bucket_of, num_segments=32
    )
    servable.store.adopt(servable, stats, index)
    prepared, src = servable.store.get(servable, 8.0)
    assert src == SOURCE_MERGED
    assert int(prepared.agg.counts.sum()) == 100


def test_streaming_capacity_and_arg_validation():
    _, stream = _stream(capacity=64)
    x = jnp.ones((60, D))
    stream.append(x)
    with pytest.raises(ValueError):
        stream.append(jnp.ones((5, D)))        # over capacity
    with pytest.raises(ValueError):
        stream.append(jnp.ones((2, D)), bogus=jnp.ones((2, 3)))


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def test_snapshot_restore_roundtrip(knn_pair, tmp_path):
    src = knn_pair()
    built = src.build(16.0)
    assert src.store.save(tmp_path / "snap") == 1

    dst = knn_pair()
    assert dst.store.restore(tmp_path / "snap", [dst]) == 1
    restored, source = dst.store.get(dst, 16.0)
    assert source == SOURCE_RESTORED
    np.testing.assert_array_equal(np.asarray(built.agg.means),
                                  np.asarray(restored.agg.means))
    np.testing.assert_array_equal(np.asarray(built.agg.counts),
                                  np.asarray(restored.agg.counts))
    np.testing.assert_array_equal(np.asarray(built.agg.perm),
                                  np.asarray(restored.agg.perm))
    # Subsequent ratios merge from the restored base, no rebuild.
    _, source2 = dst.store.get(dst, 64.0)
    assert source2 == SOURCE_MERGED
    assert dst.store.builds == 0


def test_snapshot_skips_mismatched_identity(knn_pair, tmp_path):
    src = knn_pair()
    src.build(16.0)
    src.store.save(tmp_path / "snap")
    # Different data: fingerprint mismatch -> snapshot must not be adopted.
    key = jax.random.PRNGKey(99)
    other = KNNServable(
        jax.random.normal(key, (N, D)),
        jax.random.randint(jax.random.fold_in(key, 1), (N,), 0, C),
        n_classes=C, k=3, lsh_key=jax.random.PRNGKey(7),
    )
    assert AggregateStore().restore(tmp_path / "snap", [other]) == 0
    # Different LSH key: same story.
    fresh = knn_pair()
    rekeyed = KNNServable(
        fresh.train_x, fresh.train_y, n_classes=C, k=3,
        lsh_key=jax.random.PRNGKey(123),
    )
    assert AggregateStore().restore(tmp_path / "snap", [rekeyed]) == 0


def test_save_is_atomic_and_overwrites(knn_pair, tmp_path):
    s = knn_pair()
    s.build(16.0)
    assert s.store.save(tmp_path / "snap") == 1
    assert s.store.save(tmp_path / "snap") == 1   # idempotent overwrite
    assert not (tmp_path / "snap.tmp").exists()
    assert not (tmp_path / "snap.old").exists()
    dst = knn_pair()
    assert dst.store.restore(tmp_path / "snap", [dst]) == 1


def test_empty_save_never_clobbers_a_good_snapshot(knn_pair, tmp_path):
    """A snapshot job firing before anything was built must be a no-op,
    not an empty snapshot swapped over the previous good one."""
    s = knn_pair()
    s.build(16.0)
    assert s.store.save(tmp_path / "snap") == 1
    assert AggregateStore().save(tmp_path / "snap") == 0  # nothing built
    dst = knn_pair()
    assert dst.store.restore(tmp_path / "snap", [dst]) == 1  # still intact


def test_restore_recovers_from_interrupted_save(knn_pair, tmp_path):
    """A crash between save_store's two renames leaves the previous
    snapshot at <dir>.old — restore must fall back to it; and a missing
    snapshot restores 0 instead of raising."""
    s = knn_pair()
    s.build(16.0)
    s.store.save(tmp_path / "snap")
    (tmp_path / "snap").rename(tmp_path / "snap.old")  # simulate the crash
    dst = knn_pair()
    assert dst.store.restore(tmp_path / "snap", [dst]) == 1
    assert dst.store.restore(tmp_path / "nowhere", [knn_pair()]) == 0


def test_restore_skips_incompatible_format_version(knn_pair, tmp_path):
    """A snapshot from a different format version restores nothing (cold
    start) instead of crashing the restoring server."""
    import json

    s = knn_pair()
    s.build(16.0)
    s.store.save(tmp_path / "snap")
    manifest = tmp_path / "snap" / "manifest.json"
    doc = json.loads(manifest.read_text())
    doc["version"] = 999
    manifest.write_text(json.dumps(doc))
    assert AggregateStore().restore(tmp_path / "snap", [knn_pair()]) == 0


def test_assembled_levels_are_bounded(knn_pair):
    """Pyramid memoization must not grow without bound: only the last
    ``max_assembled`` prepared levels stay resident (an evicted level
    re-derives with one merge, still exact)."""
    s = knn_pair()
    pyr = s.store.pyramid(s)
    assert pyr.max_assembled < pyr.spec.n_levels
    for level in range(pyr.spec.n_levels):
        pyr.level(level)
    assert len(pyr.assembled_levels) == pyr.max_assembled
    # Oldest levels were evicted; re-deriving one is cheap (level 0
    # re-assembles from resident stats, coarser levels are one merge) and
    # never a cold rebuild.
    evicted = pyr.spec.n_levels - pyr.max_assembled - 1
    assert evicted not in pyr.assembled_levels
    _, source = pyr.level(evicted)
    assert source == (SOURCE_MEMORY if evicted == 0 else SOURCE_MERGED)
