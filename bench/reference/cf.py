"""Plain reference of the served CF answer (AccurateML Algorithm 1, CF).

It imports nothing of the program and takes nothing the program made: from
the benchmark's own ratings and LSH key it buckets users by their centred
rating profile, aggregates each bucket, and for each sampled request
recomputes the prediction at that request's granted refinement budget:

  stage 1   shrunk Pearson weight of the active user to every non-empty
            bucket's centroid profile; the bucket's whole contribution is
            its centred sums times that weight (denominator: rater counts);
  stage 2   buckets ranked by |weight|; users taken bucket by bucket in
            rank order until the budget; a bucket whose every user was
            taken has its surrogate replaced by its users' exact terms
            (where weights at the edge of the refined prefix nearly tie,
            every order of the tied buckets is a reference answer);
  answer    p(u, i) = mean_u + num_i / den_i where den_i > 1e-8, else mean_u.

Weights shrink by co / (co + 8) on the co-rated count (significance
weighting, as the program documents).  Products run at the precision asked
for: ``highest`` for the reference, ``high`` for the control.
"""
from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.knn import bucket_ids, lsh_projections, matmul

SHRINK = 8.0


def _centre(r, m):
    mean = jnp.sum(r * m, axis=-1, keepdims=True) / jnp.maximum(
        jnp.sum(m, axis=-1, keepdims=True), 1.0)
    return (r - mean) * m, mean


@partial(jax.jit, static_argnames=("width", "base", "n_buckets", "precision"))
def aggregates(ratings, mask, a, b, *, width, base, n_buckets, precision):
    centred, _ = _centre(ratings, mask)
    fine, ids = bucket_ids(centred, a, b, width=width, base=base,
                           n_buckets=n_buckets, precision=precision)
    seg = partial(jax.ops.segment_sum, segment_ids=ids,
                  num_segments=n_buckets)
    counts = seg(jnp.ones_like(ids))
    sr, s, c = seg(ratings * mask), seg(centred), seg(mask)
    profile = sr / jnp.maximum(c, 1.0)
    order = jnp.argsort(fine, stable=True).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    return {"profile": profile, "pmask": (c > 0).astype(jnp.float32),
            "s": s, "c": c, "counts": counts, "bucket_of": ids,
            "order": order, "starts": starts.astype(jnp.int32),
            "centred": centred}


def _weights(ac, am, rows_c, rows_m, precision):
    """Shrunk Pearson weights of one active user against centred rows."""
    mm = partial(matmul, precision=precision)
    num = mm(rows_c, ac)
    a_sq = mm(rows_m, ac * ac)
    u_sq = mm(rows_c * rows_c, am)
    co = mm(rows_m, am)
    w = num / jnp.sqrt(jnp.maximum(a_sq * u_sq, 1e-12))
    return w * (co / (co + SHRINK))


@partial(jax.jit, static_argnames=("precision",))
def centroid_weights(agg, r_a, m_a, *, precision):
    """Stage 1 of one active user: shrunk Pearson weights to every bucket's
    centroid profile (0 for empty buckets)."""
    pc, _ = _centre(agg["profile"], agg["pmask"])
    ac, _ = _centre(r_a, m_a)
    w = _weights(ac, m_a, pc, agg["pmask"], precision)
    return jnp.where(agg["counts"] > 0, w, 0.0)


@partial(jax.jit, static_argnames=("budget", "precision"))
def predict(agg, mask, r_a, m_a, w, rank, *, budget, precision):
    """The prediction of one active user at ``budget``, with the buckets
    refined in the order ``rank`` (most correlated first)."""
    mm = partial(matmul, precision=precision)
    ac, mean_a = _centre(r_a, m_a)
    if budget > 0:
        cnt = agg["counts"][rank]
        cum = jnp.cumsum(cnt)
        covered = jnp.zeros_like(cnt, dtype=bool).at[rank].set(
            cum <= budget) & (agg["counts"] > 0)
        slot = jnp.arange(budget)
        rr = jnp.minimum(jnp.searchsorted(cum, slot, side="right"),
                         rank.shape[0] - 1)
        users = agg["order"][agg["starts"][rank[rr]] + slot
                             - (cum[rr] - cnt[rr])]
        use = (slot < cum[-1]) & covered[agg["bucket_of"][users]]
        rows_c = agg["centred"][users] * use[:, None]
        rows_m = mask[users] * use[:, None]
        w_u = jnp.where(use, _weights(ac, m_a, rows_c, rows_m, precision),
                        0.0)
        w_s = jnp.where(covered, 0.0, w)
        num = mm(w_s[None], agg["s"])[0] + mm(w_u[None], rows_c)[0]
        den = (mm(jnp.abs(w_s)[None], agg["c"])[0]
               + mm(jnp.abs(w_u)[None], rows_m)[0])
    else:
        num = mm(w[None], agg["s"])[0]
        den = mm(jnp.abs(w)[None], agg["c"])[0]
    return jnp.where(den > 1e-8, mean_a + num / jnp.maximum(den, 1e-8),
                     mean_a)


def orders(w: np.ndarray, counts: np.ndarray, budget: int, tie: float):
    """Bucket orders the refinement may take: buckets by |weight|, most
    correlated first (ties by bucket id), and, where buckets at the edge of
    the refined prefix have weights within ``tie`` of each other, every
    order of those buckets too.  Either side of such a near-tie is the
    algorithm's answer; which one a float32 computation lands on depends on
    its summation order."""
    key = np.where(counts > 0, np.abs(w), -np.inf)
    rank = np.argsort(-key, kind="stable")
    if budget <= 0:
        return [rank]
    cum = np.cumsum(counts[rank])
    edge = int(np.searchsorted(cum, budget, side="right"))  # first uncovered
    ks = key[rank]
    near = [j for j in range(max(edge - 2, 0), min(edge + 2, len(rank)))
            if ks[j] > 0 and min(abs(ks[j] - ks[e]) for e in
                                 (max(edge - 1, 0), min(edge, len(rank) - 1)))
            <= tie]
    if len(near) < 2 or near != list(range(near[0], near[-1] + 1)):
        return [rank]
    out = []
    for perm in itertools.permutations(rank[near[0]:near[-1] + 1]):
        alt = rank.copy()
        alt[near[0]:near[-1] + 1] = perm
        out.append(alt)
    return out


def reference_answers(cfg, data, lsh_key, actives, budgets, *, precision,
                      tie=0.0):
    """For each (active user, budget): the predictions [n_orders, I] of
    every bucket order ``orders`` allows (one unless there is a near-tie
    within ``tie`` of the largest weight)."""
    a, b = lsh_projections(lsh_key, cfg["n_items"], cfg["lsh_n_hashes"],
                           cfg["lsh_bucket_width"])
    agg = aggregates(
        data["ratings"], data["mask"], a, b, width=cfg["lsh_bucket_width"],
        base=cfg["lsh_base_buckets"], n_buckets=cfg["n_aggregates"],
        precision=precision,
    )
    counts = np.asarray(agg["counts"])
    out = []
    for r_a, m_a, budget in zip(*actives, budgets):
        r_a, m_a = jnp.asarray(r_a), jnp.asarray(m_a)
        w = centroid_weights(agg, r_a, m_a, precision=precision)
        wn = np.asarray(w)
        alts = orders(wn, counts, int(budget),
                      tie * float(np.max(np.abs(wn), initial=0.0)))
        out.append(np.stack([np.asarray(predict(
            agg, data["mask"], r_a, m_a, w, jnp.asarray(rank),
            budget=int(budget), precision=precision)) for rank in alts]))
    del agg
    return out


def compare(cfg, samples, ref_pred):
    """Numbers compared for CF (each read against its limit).

      pred_gap      widest |prediction - reference| over the sampled users
                    and every item, in stars (against the nearest of the
                    reference's predictions where a near-tie allows more
                    than one bucket order);
      bound_misses  stage-1 error bounds that are negative or not finite
                    (a mean absolute rating error).
    """
    gap, bound_misses = 0.0, 0
    for s, refs in zip(samples, ref_pred):
        gap = max(gap, min(float(np.max(np.abs(s["pred"] - r)))
                           for r in refs))
        if not (np.isfinite(s["bound"]) and s["bound"] >= 0.0):
            bound_misses += 1
    return {"pred_gap": gap, "bound_misses": bound_misses}


def control_rows(ref_pred):
    return [{"pred": p[0], "bound": 0.0} for p in ref_pred]


def check(cfg, data, lsh_key, queries, budgets, rows, *, control=False):
    """Reference predictions for the sampled (active user, budget) pairs,
    then the numbers of ``compare``; ``control`` as for kNN."""
    shape = (0, cfg["n_items"])
    r_a = np.stack([np.asarray(p[0]) for p in queries]) if queries \
        else np.zeros(shape, np.float32)
    m_a = np.stack([np.asarray(p[1]) for p in queries]) if queries \
        else np.zeros(shape, np.float32)
    ref = reference_answers(cfg, data, lsh_key, (r_a, m_a), budgets,
                            precision="highest",
                            tie=cfg["check"]["weight_tie"])
    if control:
        rows = control_rows(reference_answers(
            cfg, data, lsh_key, (r_a, m_a), budgets,
            precision=cfg["check"]["control_precision"]))
    return compare(cfg, rows, ref)
