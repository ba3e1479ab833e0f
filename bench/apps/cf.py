"""User-based CF served through ``repro``: data, servable, kernel calls.

The data generator is a copy of ``repro.data.synthetic.make_netflix_like``
(low-rank + bias + noise ratings quantized to 1..5 stars, Zipf item
popularity).  The neighbourhood shard and the pool of active users come
from one jitted call on the device; the active users are extra rows of the
same generator.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.cf import CFServable
from repro.store import AggregateStore

from bench.serving import Recording

KIND = "cf"


@partial(jax.jit, static_argnames=("n_users", "n_items", "rank", "density",
                                   "pool"))
def make_netflix_like(
    key, *, n_users, n_items, rank, density, popularity_skew, noise, pool,
):
    """-> (ratings, mask) of the ``n_users`` shard, then of ``pool`` active
    users; ratings are 0 where missing."""
    total = n_users + pool
    ku, ki, kb, kc, km, kn = jax.random.split(key, 6)
    u = jax.random.normal(ku, (total, rank)) / jnp.sqrt(rank)
    v = jax.random.normal(ki, (n_items, rank)) / jnp.sqrt(rank)
    user_bias = jax.random.normal(kb, (total, 1)) * 0.5
    item_bias = jax.random.normal(kc, (1, n_items)) * 0.5
    raw = 3.0 + 1.8 * (u @ v.T) + user_bias + item_bias
    raw = raw + noise * jax.random.normal(kn, (total, n_items))
    ratings = jnp.clip(jnp.round(raw), 1.0, 5.0)
    pop = (1.0 + jnp.arange(n_items, dtype=jnp.float32)) ** (-popularity_skew)
    pop = jnp.clip(pop / jnp.mean(pop) * density, 0.0, 0.95)
    mask = (jax.random.uniform(km, (total, n_items)) < pop[None, :]).astype(
        jnp.float32)
    ratings = (ratings * mask).astype(jnp.float32)
    return ratings[pool:], mask[pool:], ratings[:pool], mask[:pool]


def make_data(cfg: dict, key) -> dict:
    ratings, mask, active, active_mask = make_netflix_like(
        key, n_users=cfg["n_users"], n_items=cfg["n_items"],
        rank=cfg["rank"], density=cfg["density"],
        popularity_skew=cfg["popularity_skew"], noise=cfg["noise"],
        pool=cfg["active_pool"],
    )
    return {"ratings": ratings, "mask": mask, "active": active,
            "active_mask": active_mask}


class BenchCF(Recording, CFServable):
    pass


def make_servable(cfg: dict, data: dict, lsh_key):
    return BenchCF(
        data["ratings"], data["mask"], lsh_key=lsh_key,
        n_hashes=cfg["lsh_n_hashes"], bucket_width=cfg["lsh_bucket_width"],
        store=AggregateStore(),
    )


def payloads(data: dict):
    """Host copies of the active users: a request arrives from a client."""
    return np.asarray(data["active"]), np.asarray(data["active_mask"])


def payload(pool, i: int) -> tuple:
    return (pool[0][i], pool[1][i])


def realized(prepared) -> dict:
    counts = prepared.agg.counts
    return {
        "aggregates": int(counts.shape[0]),
        "nonempty_aggregates": int(jnp.sum(counts > 0)),
    }


def answer_row(outputs, row: int) -> dict:
    pred, bound = outputs
    return {"pred": np.asarray(pred[row]), "bound": float(bound[row])}


def kernel_calls(cfg: dict, n: int, refine_budget: int, stage: str) -> list:
    """Kernel calls one run of the map makes, with algorithmic shapes."""
    k_aggs, items = cfg["n_aggregates"], cfg["n_items"]
    calls = [("cf_weights", {"q": n, "u": k_aggs, "i": items})]
    if stage == "stage2":
        calls.append(("cf_refine", {"q": n, "b": refine_budget, "i": items}))
    return calls


def map_work(cfg: dict, n: int, refine_budget: int) -> dict:
    """Algorithmic work of answering ``n`` active users: centroid weights
    and the surrogate sums (stage 1), then the refined users' rows."""
    k_aggs, items = cfg["n_aggregates"], cfg["n_items"]
    stage1 = (12.0 * n * k_aggs * items,
              4.0 * (5 * k_aggs * items + 5 * n * items))
    rows = n * refine_budget
    stage2 = (12.0 * rows * items, 4.0 * rows * (2 * items + 1))
    return {"stage1": stage1, "stage2": stage2}
