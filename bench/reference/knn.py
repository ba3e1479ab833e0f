"""Plain reference of the served kNN answer (AccurateML Algorithm 1, kNN).

It imports nothing of the program and takes nothing the program made: from
the benchmark's own data and LSH key it draws the p-stable projections,
buckets and aggregates the training points, and for each sampled request
recomputes the answer at that request's granted refinement budget:

  stage 1   distances from the query to every non-empty bucket centroid;
  stage 2   buckets ranked by distance (Def. 4: correlation = -distance),
            original points taken bucket by bucket in rank order until the
            budget; a bucket whose every point was taken is replaced by its
            points, the rest keep their centroid;
  answer    the k nearest candidates and the majority vote of their labels
            (ties to the lowest class id).

The semantics follow the program's documented contract: nested LSH ids
(``fine = signature mod base_buckets``, ``id = fine // (base / n)``), the
points of a bucket in fine-id order, the vote rule.  Distances use the
expanded form ``|q|^2 - 2 q.x + |x|^2`` with the matrix products at the
precision asked for (``matmul``): ``highest`` for the reference, ``high``
for the control (the next precision below float32 at highest).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Signature primes of the LSH family: a bucket signature is
# sum_j h_j * prime_j (mod 2^32), reduced mod the finest bucket count.
PRIMES = (2654435761, 2246822519, 3266489917, 668265263, 374761393,
          2654435789, 1103515245, 2971215073)
BIG = 3.0e38


def matmul(a, b, precision: str):
    """``a @ b`` at a named precision, the same on every backend.

    ``highest`` is float32; ``high`` is emulated as three bfloat16 passes
    (each operand split into a bfloat16 head and a bfloat16 tail; head*head
    + head*tail + tail*head, accumulated in float32), which is what the
    chip's ``high`` does; ``bfloat16`` is the head*head pass alone.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    mm = partial(jnp.matmul, precision="highest")
    if precision == "highest":
        return mm(a, b)

    def split(x):
        head = x.astype(jnp.bfloat16).astype(jnp.float32)
        return head, (x - head).astype(jnp.bfloat16).astype(jnp.float32)

    ah, al = split(a)
    bh, bl = split(b)
    if precision == "bfloat16":
        return mm(ah, bh)
    if precision == "high":
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


def lsh_projections(lsh_key, n_features: int, n_hashes: int, width: float):
    ka, kb = jax.random.split(lsh_key)
    a = jax.random.normal(ka, (n_features, n_hashes), dtype=jnp.float32)
    b = jax.random.uniform(kb, (n_hashes,), minval=0.0, maxval=width,
                           dtype=jnp.float32)
    return a, b


def bucket_ids(feats, a, b, *, width, base, n_buckets, precision):
    """(fine ids, served ids) of each row."""
    proj = matmul(feats, a, precision) + b
    h = jnp.floor(proj / width).astype(jnp.int32)
    primes = jnp.asarray(PRIMES[: a.shape[1]], dtype=jnp.uint32)
    sig = jnp.sum(h.astype(jnp.uint32) * primes[None, :], axis=-1)
    fine = (sig % jnp.uint32(base)).astype(jnp.int32)
    return fine, fine // jnp.int32(base // n_buckets)


@partial(jax.jit, static_argnames=(
    "width", "base", "n_buckets", "n_classes", "precision"))
def aggregates(train_x, train_y, a, b, *, width, base, n_buckets, n_classes,
               precision):
    fine, ids = bucket_ids(train_x, a, b, width=width, base=base,
                           n_buckets=n_buckets, precision=precision)
    counts = jax.ops.segment_sum(jnp.ones_like(ids), ids,
                                 num_segments=n_buckets)
    sums = jax.ops.segment_sum(train_x, ids, num_segments=n_buckets)
    means = sums / jnp.maximum(counts, 1)[:, None].astype(jnp.float32)
    hist = jax.ops.segment_sum(jax.nn.one_hot(train_y, n_classes), ids,
                               num_segments=n_buckets)
    labels = jnp.argmax(hist, axis=-1).astype(jnp.int32)
    order = jnp.argsort(fine, stable=True).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    return {"means": means, "counts": counts, "labels": labels,
            "order": order, "starts": starts.astype(jnp.int32)}


def _sq_dists(q, rows, precision):
    cross = matmul(rows, q, precision)
    return jnp.maximum(
        jnp.sum(q * q) - 2.0 * cross + jnp.sum(rows * rows, axis=-1), 0.0
    )


@partial(jax.jit, static_argnames=("budget", "k_out", "precision"))
def answers(agg, train_x, train_y, queries, *, budget, k_out, precision):
    """[S, D] queries at one refinement budget -> (d, labels) [S, k_out]."""

    def one(q):
        d_cent = _sq_dists(q, agg["means"], precision)
        d_cent = jnp.where(agg["counts"] > 0, d_cent, BIG)
        if budget == 0:
            neg, pos = jax.lax.top_k(-d_cent, k_out)
            return -neg, agg["labels"][pos]
        rank = jnp.argsort(d_cent, stable=True)
        cnt = agg["counts"][rank]
        cum = jnp.cumsum(cnt)
        covered = jnp.zeros_like(cnt, dtype=bool).at[rank].set(cum <= budget)
        slot = jnp.arange(budget)
        r = jnp.searchsorted(cum, slot, side="right")
        r = jnp.minimum(r, rank.shape[0] - 1)
        within = slot - (cum[r] - cnt[r])
        pts = agg["order"][agg["starts"][rank[r]] + within]
        valid = slot < cum[-1]
        pts = jnp.where(valid, pts, 0)
        d_pts = jnp.where(valid, _sq_dists(q, train_x[pts], precision), BIG)
        cand_d = jnp.concatenate(
            [jnp.where(covered | (agg["counts"] == 0), BIG, d_cent), d_pts])
        cand_l = jnp.concatenate([agg["labels"], train_y[pts]])
        neg, pos = jax.lax.top_k(-cand_d, k_out)
        return -neg, cand_l[pos]

    return jax.lax.map(one, queries)


def vote(d: np.ndarray, labels: np.ndarray, n_classes: int) -> int:
    """Majority label among real candidates; ties go to the lowest id."""
    counts = np.bincount(labels[d < BIG / 2], minlength=n_classes)
    return int(np.argmax(counts))


def reference_answers(cfg, data, lsh_key, queries, budgets, *, precision,
                      k_out):
    """Reference (d, labels) [S, k_out] for each (query, budget)."""
    a, b = lsh_projections(lsh_key, cfg["n_features"], cfg["lsh_n_hashes"],
                           cfg["lsh_bucket_width"])
    agg = aggregates(
        data["train_x"], data["train_y"], a, b,
        width=cfg["lsh_bucket_width"], base=cfg["lsh_base_buckets"],
        n_buckets=cfg["n_aggregates"], n_classes=cfg["n_classes"],
        precision=precision,
    )
    budgets = np.asarray(budgets)
    d_out = np.zeros((len(queries), k_out), np.float32)
    l_out = np.zeros((len(queries), k_out), np.int32)
    for budget in np.unique(budgets):
        sel = np.flatnonzero(budgets == budget)
        d, lab = answers(agg, data["train_x"], data["train_y"],
                         jnp.asarray(queries[sel]), budget=int(budget),
                         k_out=k_out, precision=precision)
        d_out[sel], l_out[sel] = np.asarray(d), np.asarray(lab)
    del agg
    return d_out, l_out


def compare(cfg, samples, ref_d, ref_l, x2_max, tol):
    """Numbers compared for kNN (each read against its limit).

    ``samples``: dicts with the query ``q`` and the program's answer row
    (``d``, ``labels``, ``vote``, ``bound``).  ``tol`` is the distance
    tolerance in units of ``|q|^2 + max |x|^2``, the scale of the terms the
    squared distances are formed from.

      dist_gap        widest |d - d_ref| over the k neighbours, in scale units;
      label_misses    neighbours whose label no reference candidate within
                      ``tol`` of that distance carries (an exact count);
      vote_misses     answers whose vote is not the majority of their labels;
      bound_misses    stage-1 error bounds outside [0, 1] (a label divergence).
    """
    k = cfg["k"]
    gap, label_misses, vote_misses, bound_misses = 0.0, 0, 0, 0
    for s, dr, lr in zip(samples, ref_d, ref_l):
        q = np.asarray(s["q"], np.float64)
        scale = float(q @ q) + x2_max
        d, lab = s["d"][:k], s["labels"][:k]
        gap = max(gap, float(np.max(np.abs(d - dr[:k]))) / scale)
        for j in range(k):
            if lab[j] == lr[j]:
                continue
            near = np.abs(dr - d[j]) <= tol * scale
            if not np.any(near & (lr == lab[j])):
                label_misses += 1
        if s["vote"] != vote(d, lab, cfg["n_classes"]):
            vote_misses += 1
        if not 0.0 <= s["bound"] <= 1.0:
            bound_misses += 1
    return {"dist_gap": gap, "label_misses": label_misses,
            "vote_misses": vote_misses, "bound_misses": bound_misses}


def control_rows(cfg, ref_d, ref_l):
    """Answer rows of the reference computed at the control's precision,
    put where the program's rows go."""
    rows = []
    for d, lab in zip(ref_d, ref_l):
        rows.append({"d": d, "labels": lab,
                     "vote": vote(d[:cfg["k"]], lab[:cfg["k"]],
                                  cfg["n_classes"]),
                     "bound": 0.0})
    return rows


def check(cfg, data, lsh_key, queries, budgets, rows, *, control=False):
    """Reference answers for the sampled (query, budget) pairs, then the
    numbers of ``compare``.  With ``control`` the rows compared are the
    reference's own at ``cfg["check"]["control_precision"]``."""
    q = np.stack([np.asarray(p[0], np.float32) for p in queries]) \
        if queries else np.zeros((0, cfg["n_features"]), np.float32)
    k_out = cfg["k"] + 4
    ref_d, ref_l = reference_answers(cfg, data, lsh_key, q, budgets,
                                     precision="highest", k_out=k_out)
    if control:
        cd, cl = reference_answers(
            cfg, data, lsh_key, q, budgets,
            precision=cfg["check"]["control_precision"], k_out=k_out)
        rows = control_rows(cfg, cd, cl)
    x2_max = float(jnp.max(jnp.sum(jnp.square(data["train_x"]), axis=1)))
    samples = [dict(row, q=qi) for row, qi in zip(rows, q)]
    return compare(cfg, samples, ref_d, ref_l, x2_max,
                   tol=cfg["check"]["limits"]["dist_gap"])
