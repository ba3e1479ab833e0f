"""kNN classification served through ``repro``: data, servable, kernel calls.

The data generator is a copy of ``repro.data.synthetic.make_mfeat_like``
(the yardstick keeps its own, so a change to the program cannot change
the data it is measured on).  Training points, labels and the held-out
query pool come from one jitted call on the device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.knn import KNNServable
from repro.store import AggregateStore

from bench.serving import Recording

KIND = "knn"


@partial(jax.jit, static_argnames=(
    "n_points", "n_features", "n_classes", "modes_per_class", "pool",
))
def make_mfeat_like(
    key, *, n_points, n_features, n_classes, modes_per_class, class_sep,
    mode_scale, pool,
):
    """Multi-modal Gaussian-mixture classification data: ``pool`` held-out
    queries and ``n_points`` training points from the same mixture."""
    kc, kmode, km, kx = jax.random.split(key, 4)
    total = n_points + pool
    labels = jax.random.randint(kc, (total,), 0, n_classes)
    mode_idx = jax.random.randint(kmode, (total,), 0, modes_per_class)
    mode_means = (
        jax.random.normal(km, (n_classes, modes_per_class, n_features))
        * class_sep
    )
    noise = jax.random.normal(kx, (total, n_features)) * mode_scale
    x = (mode_means[labels, mode_idx] + noise).astype(jnp.float32)
    y = labels.astype(jnp.int32)
    return x[pool:], y[pool:], x[:pool], y[:pool]


def make_data(cfg: dict, key) -> dict:
    train_x, train_y, pool_x, pool_y = make_mfeat_like(
        key, n_points=cfg["n_points"], n_features=cfg["n_features"],
        n_classes=cfg["n_classes"], modes_per_class=cfg["modes_per_class"],
        class_sep=cfg["class_sep"], mode_scale=cfg["mode_scale"],
        pool=cfg["query_pool"],
    )
    return {"train_x": train_x, "train_y": train_y, "pool_x": pool_x}


class BenchKNN(Recording, KNNServable):
    pass


def make_servable(cfg: dict, data: dict, lsh_key):
    return BenchKNN(
        data["train_x"], data["train_y"], n_classes=cfg["n_classes"],
        k=cfg["k"], lsh_key=lsh_key, n_hashes=cfg["lsh_n_hashes"],
        bucket_width=cfg["lsh_bucket_width"], store=AggregateStore(),
    )


def payloads(data: dict) -> np.ndarray:
    """Host copies of the pooled queries: a request arrives from a client."""
    return np.asarray(data["pool_x"])


def payload(pool: np.ndarray, i: int) -> tuple:
    return (pool[i],)


def realized(prepared) -> dict:
    counts = prepared.agg.counts
    return {
        "aggregates": int(prepared.agg.means.shape[0]),
        "nonempty_aggregates": int(jnp.sum(counts > 0)),
    }


def answer_row(outputs, row: int) -> dict:
    """The compared parts of one request's answer (host arrays)."""
    d, lab, vote, bound = outputs
    return {"d": np.asarray(d[row]), "labels": np.asarray(lab[row]),
            "vote": int(vote[row]), "bound": float(bound[row])}


def kernel_calls(cfg: dict, n: int, refine_budget: int, stage: str) -> list:
    """Kernel calls one run of the map makes, with the algorithmic shapes:
    ``n`` real queries (batch padding is not work a user asked for)."""
    k_aggs, d = cfg["n_aggregates"], cfg["n_features"]
    if stage == "stage1":
        return [("distance_topk", {"q": n, "n": k_aggs, "d": d,
                                   "k": cfg["k"] + 1})]
    return [
        ("knn_distance", {"q": n, "n": k_aggs, "d": d}),
        ("refine_distances", {"q": n, "b": refine_budget, "d": d}),
    ]


def map_work(cfg: dict, n: int, refine_budget: int) -> dict:
    """Algorithmic work of answering ``n`` queries: one pass over the
    aggregates (stage 1, whose correlations stage 2 reuses) plus the
    refined rows; each side as (flops, bytes)."""
    k_aggs, d = cfg["n_aggregates"], cfg["n_features"]
    stage1 = (2.0 * n * k_aggs * d + 3.0 * n * k_aggs,
              4.0 * k_aggs * (d + 2) + 4.0 * n * d)
    rows = n * refine_budget
    stage2 = (3.0 * rows * d, 4.0 * rows * (d + 1))
    return {"stage1": stage1, "stage2": stage2}
