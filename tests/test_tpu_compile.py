"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret-mode parity (``test_kernels.py``, ``test_decode.py``) runs the
kernel bodies on the CPU and cannot see what Mosaic refuses: block shapes
off the (8, 128) tiling, scalar-prefetch operands larger than SMEM, or
unsupported in-kernel reshapes.  These tests lower and compile each kernel
for one chip of a described ``v5e:2x2`` topology, at the shapes
``chip_smoke.py`` drives (its ``KNN_*``, ``CF_*`` and ``LM_*`` constants):

  * kNN at the paper's scale: 2.3M x 64 float32 points, 131,072 aggregates,
    a 4-query batch, k = 5 (6 with the error bound's extra candidate); the
    stage-2 row walk also at the benchmark cells' 217 features;
  * CF at MovieLens-1M's shape: 6,040 users x 3,706 items, 256 aggregates;
  * decode at qwen3-8b head shapes: 8 KV heads, group 4, head_dim 128,
    bucket capacity 128, 32 buckets, 4 slots.

Nothing runs: a compile that passes here is not a chip run.  The topology
is described inside a module fixture (never at import), so every xdist
worker collects the same tests and only the worker given this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.refine import eps_to_budget
from repro.kernels.agg_refine import agg_refine_attention_pallas
from repro.kernels.cf_refine import cf_refine_pallas
from repro.kernels.cf_weights import cf_weights_pallas
from repro.kernels.distance_topk import distance_topk_pallas
from repro.kernels.knn_distance import knn_distance_pallas
from repro.kernels.refine_distances import refine_distances_pallas
from repro.kernels.topk_stream import candidate_topk_pallas

KNN_POINTS, KNN_D, KNN_AGGS, BATCH, K = 2_300_000, 64, 131_072, 4, 5
CF_USERS, CF_ITEMS, CF_AGGS = 6_040, 3_706, 256
EPS_MAX = 0.32
HKV, GROUP, HEAD_DIM, CAP, LM_BUCKETS = 8, 4, 128, 128, 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, I32, BOOL, BF16 = jnp.float32, jnp.int32, jnp.bool_, jnp.bfloat16


@pytest.mark.parametrize("n,k", [(KNN_POINTS, K), (KNN_AGGS, K + 1)])
def test_distance_topk_l2(one_chip, n, k):
    """Exact map over every point, and stage 1 over the aggregates."""
    _compile(
        lambda q, p, lab, v: distance_topk_pallas(q, p, lab, v, k=k),
        one_chip, ((BATCH, KNN_D), F32), ((n, KNN_D), F32), ((n,), I32),
        ((n,), I32),
    )


def test_distance_topk_dot_bucket_selection(one_chip):
    """Decode stage 1: per-slot top-R buckets by pooled-query correlation
    (vmapped over slots, as ``select_buckets`` calls it)."""
    width = HKV * HEAD_DIM
    labels = jnp.arange(LM_BUCKETS, dtype=I32)

    def select(qp, cents, valid):
        return jax.vmap(lambda q, c, v: distance_topk_pallas(
            q[None], c, labels, v, k=LM_BUCKETS, metric="dot"
        ))(qp, cents, valid)

    _compile(
        select, one_chip, ((BATCH, width), F32),
        ((BATCH, LM_BUCKETS, width), F32), ((BATCH, LM_BUCKETS), I32),
    )


def test_knn_distance_stage2_correlations(one_chip):
    _compile(
        knn_distance_pallas, one_chip, ((BATCH, KNN_D), F32),
        ((KNN_AGGS, KNN_D), F32),
    )


@pytest.mark.parametrize("m", [KNN_AGGS, eps_to_budget(KNN_POINTS, EPS_MAX)])
def test_candidate_topk(one_chip, m):
    """Stage-2 finalize: centroid seed, then the refined candidates."""
    _compile(
        lambda d, lab, d0, l0: candidate_topk_pallas(d, lab, d0, l0, k=K + 1),
        one_chip, ((BATCH, m), F32), ((BATCH, m), I32),
        ((BATCH, K + 1), F32), ((BATCH, K + 1), I32),
    )


def _compile_refine(sharding, d, b, table_shape):
    return _compile(
        refine_distances_pallas, sharding, ((BATCH, d), F32),
        (table_shape, F32), ((BATCH, b), I32), ((BATCH, b), BOOL),
    )


def _row_table_shape(d):
    """The row table ``KNNServable`` holds: [N, 1, D padded to 128]."""
    return KNN_POINTS, 1, -(-d // 128) * 128


@pytest.mark.parametrize(
    "b", [eps_to_budget(KNN_POINTS, EPS_MAX), KNN_POINTS],
    ids=["eps_max", "full"],
)
def test_refine_distances(one_chip, b):
    compiled = _compile_refine(one_chip, KNN_D, b, _row_table_shape(KNN_D))
    # From the row table the served path passes, the call makes no copy of
    # the table: the rows are copied straight from it, a block at a time.
    table = KNN_POINTS * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * table


# The knn-mfeat2.3m cells: 217 float32 features, refined at eps 0.08.
CELL_D, CELL_EPS = 217, 0.08


@pytest.mark.parametrize(
    "b", [eps_to_budget(KNN_POINTS, CELL_EPS), KNN_POINTS],
    ids=["eps_0.08", "full"],
)
def test_refine_distances_cell_shapes(one_chip, b):
    """One row-walk call with no copy of the table, no chunk loop around
    it, and an instruction name the benchmark's kernel reader matches."""
    import re

    from bench import registry

    compiled = _compile_refine(one_chip, CELL_D, b, _row_table_shape(CELL_D))
    table = KNN_POINTS * CELL_D * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * table
    hlo = compiled.as_text()
    assert " while(" not in hlo
    assert any(re.search(p, hlo)
               for p in registry.kernel("refine_distances").MATCH)


def test_refine_distances_plain_table(one_chip):
    """A caller that passes the plain [N, D] table pays the row table's
    layout on each call: XLA's row-major copy of the feature-major table
    and the padded row table, each once per call and no more."""
    b = eps_to_budget(KNN_POINTS, CELL_EPS)
    compiled = _compile_refine(one_chip, CELL_D, b, (KNN_POINTS, CELL_D))
    row_table = KNN_POINTS * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 2.1 * row_table


@pytest.mark.parametrize(
    "b", [eps_to_budget(CF_USERS, EPS_MAX), CF_USERS], ids=["eps_max", "full"]
)
def test_cf_refine(one_chip, b):
    _compile(
        lambda a, am, r, m, i, u: cf_refine_pallas(
            a, am, r, m, i, u, shrink=8.0
        ),
        one_chip, ((BATCH, CF_ITEMS), F32), ((BATCH, CF_ITEMS), F32),
        ((CF_USERS, CF_ITEMS), F32), ((CF_USERS, CF_ITEMS), F32),
        ((BATCH, b), I32), ((BATCH, b), BOOL),
    )


@pytest.mark.parametrize("u", [CF_AGGS, CF_USERS], ids=["stage1", "exact"])
def test_cf_weights(one_chip, u):
    _compile(
        cf_weights_pallas, one_chip, ((BATCH, CF_ITEMS), F32),
        ((BATCH, CF_ITEMS), F32), ((u, CF_ITEMS), F32), ((u, CF_ITEMS), F32),
    )


def test_agg_refine(one_chip):
    slots = (BATCH, LM_BUCKETS, CAP, HKV, HEAD_DIM)
    compiled = _compile(
        lambda q, k, v, c, t, u: agg_refine_attention_pallas(
            q, k, v, c, t, u, scale=HEAD_DIM ** -0.5
        ),
        one_chip, ((BATCH, HKV, GROUP, HEAD_DIM), F32), (slots, BF16),
        (slots, BF16), ((BATCH, LM_BUCKETS), I32),
        ((BATCH, LM_BUCKETS), I32), ((BATCH, LM_BUCKETS), BOOL),
    )
    # The bucket blocks index the cache as stored: no relayout copy.
    assert compiled.memory_analysis().temp_size_in_bytes == 0
