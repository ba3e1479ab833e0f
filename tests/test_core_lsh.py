"""Unit + property tests for the p-stable LSH layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import lsh as lsh_lib


def test_bucket_ids_bounded():
    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, (512, 24))
    cfg = lsh_lib.LSHConfig(n_hashes=4, bucket_width=4.0, n_buckets=37)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(1), 24, cfg)
    ids = lsh_lib.bucket_ids(data, params)
    assert ids.shape == (512,)
    assert ids.dtype == jnp.int32
    assert int(ids.min()) >= 0 and int(ids.max()) < 37


def test_identical_points_same_bucket():
    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, (16, 8))
    dup = jnp.concatenate([data, data], axis=0)
    cfg = lsh_lib.LSHConfig(n_hashes=6, bucket_width=2.0, n_buckets=64)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(3), 8, cfg)
    ids = lsh_lib.bucket_ids(dup, params)
    np.testing.assert_array_equal(np.asarray(ids[:16]), np.asarray(ids[16:]))


def test_locality_property():
    """Definition 2: near pairs collide much more often than far pairs."""
    key = jax.random.PRNGKey(42)
    base = jax.random.normal(key, (400, 16)) * 4.0
    near = base + 0.05 * jax.random.normal(jax.random.PRNGKey(1), base.shape)
    far = base + 8.0 * jax.random.normal(jax.random.PRNGKey(2), base.shape)
    cfg = lsh_lib.LSHConfig(n_hashes=4, bucket_width=4.0, n_buckets=128)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(5), 16, cfg)
    ids_b = lsh_lib.bucket_ids(base, params)
    ids_n = lsh_lib.bucket_ids(near, params)
    ids_f = lsh_lib.bucket_ids(far, params)
    p_near = float(jnp.mean((ids_b == ids_n).astype(jnp.float32)))
    p_far = float(jnp.mean((ids_b == ids_f).astype(jnp.float32)))
    assert p_near > 0.5, p_near
    assert p_near > p_far + 0.3, (p_near, p_far)


def test_raw_hash_matches_definition():
    """h(d) = floor((a.d + b)/w) elementwise (Eq. 1)."""
    key = jax.random.PRNGKey(0)
    data = jax.random.normal(key, (32, 8))
    cfg = lsh_lib.LSHConfig(n_hashes=3, bucket_width=1.7, n_buckets=16)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(1), 8, cfg)
    h = lsh_lib.raw_hashes(data, params)
    expected = np.floor(
        (np.asarray(data) @ np.asarray(params.a) + np.asarray(params.b))
        / cfg.bucket_width
    ).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(h), expected)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(min_value=8, max_value=300),
    r=st.floats(min_value=1.0, max_value=64.0),
)
def test_config_for_compression_targets_ratio(n, r):
    cfg = lsh_lib.config_for_compression(n, r)
    assert cfg.n_buckets >= 1
    assert abs(cfg.n_buckets - n / r) <= 1.0


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(min_value=4, max_value=128),
    d=st.integers(min_value=1, max_value=32),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_bucket_ids_always_in_range(n, d, seed):
    data = jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * 10.0
    cfg = lsh_lib.LSHConfig(n_hashes=2, bucket_width=3.0, n_buckets=17)
    params = lsh_lib.init_lsh(jax.random.PRNGKey(seed + 1), d, cfg)
    ids = np.asarray(lsh_lib.bucket_ids(data, params))
    assert ids.min() >= 0 and ids.max() < 17
