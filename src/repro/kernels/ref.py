"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth: kernel tests sweep shapes and
dtypes and assert allclose against these, and `ops.py` falls back to them on
backends without Pallas support (CPU tests run kernels in interpret mode AND
compare against these).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.topk_stream import BIG


def _pad_candidates(dists: jax.Array, labels: jax.Array, k: int):
    """Pad the candidate axis to >= k with the BIG sentinel so selections
    over fewer than k candidates return BIG-padded slots (the kernels get
    this for free from tile padding; `lax.top_k` would raise)."""
    short = k - dists.shape[-1]
    if short > 0:
        dists = jnp.pad(dists, ((0, 0), (0, short)), constant_values=BIG)
        labels = jnp.pad(labels, ((0, 0), (0, short)))
    return dists, labels


def knn_distance(queries: jax.Array, points: jax.Array) -> jax.Array:
    """Squared L2 distance matrix. [Q,D],[N,D] -> [Q,N] float32.

    Expanded form (|q|^2 - 2 q.p + |p|^2) so the hot loop is one matmul —
    the same contraction the Pallas kernel tiles onto the MXU.
    """
    q = queries.astype(jnp.float32)
    p = points.astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)        # [Q,1]
    p2 = jnp.sum(p * p, axis=-1, keepdims=True).T      # [1,N]
    cross = q @ p.T                                    # [Q,N]
    return jnp.maximum(q2 - 2.0 * cross + p2, 0.0)


def candidate_topk(
    dists: jax.Array, labels: jax.Array,
    init_d: jax.Array | None = None, init_l: jax.Array | None = None,
    *, k: int,
) -> tuple[jax.Array, jax.Array]:
    """Per-query k smallest (distance, label) pairs from [Q, M] candidates.

    ``init_d``/``init_l`` [Q, k] seed the selection (a previously merged
    running best); seeding-then-selecting equals one selection over the
    concatenation because both orders are the k smallest under the same
    (value, position) tie-break — the contract the fused stage-2 finalize
    relies on.
    """
    if init_d is not None:
        dists = jnp.concatenate([init_d, dists], axis=1)
        labels = jnp.concatenate([init_l, labels], axis=1)
    dists, labels = _pad_candidates(dists, labels, k)
    neg, idx = jax.lax.top_k(-dists.astype(jnp.float32), k)
    return -neg, jnp.take_along_axis(labels, idx, axis=-1).astype(jnp.int32)


def distance_topk(
    queries: jax.Array, points: jax.Array, labels: jax.Array,
    valid: jax.Array | None = None, *, k: int, metric: str = "l2",
) -> tuple[jax.Array, jax.Array]:
    """Fused distance + top-k oracle: [Q,D],[N,D],[N] -> ([Q,k], [Q,k]).

    Semantically `knn_distance` then `top_k`; the Pallas kernel never
    materializes the [Q, N] intermediate.  ``valid`` masks points (padding,
    empty buckets) out with the BIG sentinel.

    ``metric="dot"`` scores by *negated* dot product, so the k smallest
    scores are the k most-correlated points — the decode path's stage-1
    bucket selection (Definition 4's correlations) rides the same fused
    kernel as kNN.  A selected score >= BIG/2 is a padding slot, not a
    real candidate.
    """
    if metric == "dot":
        q = queries.astype(jnp.float32)
        p = points.astype(jnp.float32)
        d = -(q @ p.T)
    elif metric == "l2":
        d = knn_distance(queries, points)
    else:
        raise ValueError(f"metric {metric!r}: expected 'l2' or 'dot'")
    if valid is not None:
        d = jnp.where(valid[None, :], d, BIG)
    lab = jnp.broadcast_to(labels[None, :].astype(jnp.int32), d.shape)
    d, lab = _pad_candidates(d, lab, k)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(lab, idx, axis=-1)


def refine_distances(
    queries: jax.Array, train_x: jax.Array,
    idx: jax.Array, valid: jax.Array,
) -> jax.Array:
    """Per-query exact distances to selected originals, BIG-masked padding.

    [Q,D],[N,D],[Q,B],[Q,B] -> [Q,B].  The oracle gathers [Q,B,D]; the
    Pallas kernel reads each selected row straight from HBM instead.  The
    table may also be its row table ([N, 1, Dp], features zero-padded; see
    ``refine_distances.row_table``), of which the first D features count.
    """
    if train_x.ndim == 3:
        train_x = train_x[:, 0, :queries.shape[1]]
    qf = queries.astype(jnp.float32)
    ref_x = train_x.astype(jnp.float32)[idx]                # [Q, B, D]
    q2 = jnp.sum(qf * qf, axis=-1)                          # [Q]
    x2 = jnp.sum(ref_x * ref_x, axis=-1)                    # [Q, B]
    cross = jnp.einsum("qd,qbd->qb", qf, ref_x)
    d = jnp.maximum(q2[:, None] - 2.0 * cross + x2, 0.0)
    return jnp.where(valid, d, BIG)


def cf_refine(
    active: jax.Array, active_mask: jax.Array,
    ratings: jax.Array, mask: jax.Array,
    idx: jax.Array, use: jax.Array,
    *, shrink: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """CF stage-2 refinement oracle (the original einsum formulation).

    Returns (w_ref [Q,B], num_delta [Q,I], den_delta [Q,I]): shrunk Pearson
    weights of each query against its selected candidate users, and the
    weighted neighbourhood sums those candidates contribute.  ``use`` gates
    candidates (selection padding / partially covered buckets) to zero.
    """
    centred_all = (ratings - _user_means(ratings, mask)) * mask
    ref_m = mask[idx] * use[..., None]                      # [Q, B, I]
    ref_c = centred_all[idx] * use[..., None]

    af = active.astype(jnp.float32)
    am = active_mask.astype(jnp.float32)
    a_mean = jnp.sum(af * am, axis=1, keepdims=True) / jnp.maximum(
        jnp.sum(am, axis=1, keepdims=True), 1.0
    )
    ac = (af - a_mean) * am                                 # [Q, I]

    w_num = jnp.einsum("qi,qbi->qb", ac, ref_c)
    a_sq = jnp.einsum("qi,qbi->qb", ac * ac, ref_m)
    u_sq = jnp.einsum("qi,qbi->qb", am, ref_c * ref_c)
    w_ref = w_num / jnp.sqrt(jnp.maximum(a_sq * u_sq, 1e-12))
    co_ref = jnp.einsum("qi,qbi->qb", am, ref_m)
    w_ref = w_ref * (co_ref / (co_ref + shrink))
    w_ref = jnp.where(use, w_ref, 0.0)                      # [Q, B]

    num_delta = jnp.einsum("qb,qbi->qi", w_ref, ref_c)
    den_delta = jnp.einsum("qb,qbi->qi", jnp.abs(w_ref), ref_m)
    return w_ref, num_delta, den_delta


def _user_means(ratings: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.sum(ratings * mask, axis=1, keepdims=True) / jnp.maximum(
        jnp.sum(mask, axis=1, keepdims=True), 1.0
    )


def lsh_hash(
    data: jax.Array, a: jax.Array, b: jax.Array, width: float
) -> jax.Array:
    """p-stable hashes floor((data @ a + b)/w). [N,D],[D,H],[H] -> [N,H] int32."""
    proj = data.astype(jnp.float32) @ a.astype(jnp.float32) + b[None, :]
    return jnp.floor(proj / width).astype(jnp.int32)


def cf_weights(
    active: jax.Array, active_mask: jax.Array,
    users: jax.Array, users_mask: jax.Array,
) -> jax.Array:
    """Masked Pearson weights between active users and neighbour users.

    [Q,I],[Q,I],[U,I],[U,I] -> [Q,U] float32, over co-rated items only.
    """
    a = active.astype(jnp.float32)
    am = active_mask.astype(jnp.float32)
    u = users.astype(jnp.float32)
    um = users_mask.astype(jnp.float32)

    a_mean = jnp.sum(a * am, axis=1, keepdims=True) / jnp.maximum(
        jnp.sum(am, axis=1, keepdims=True), 1.0
    )
    u_mean = jnp.sum(u * um, axis=1, keepdims=True) / jnp.maximum(
        jnp.sum(um, axis=1, keepdims=True), 1.0
    )
    ac = (a - a_mean) * am                             # centred, masked
    uc = (u - u_mean) * um

    num = ac @ uc.T                                    # [Q,U]
    a_sq = (ac * ac) @ um.T                            # sum over co-rated
    u_sq = am @ (uc * uc).T
    den = jnp.sqrt(jnp.maximum(a_sq * u_sq, 1e-12))
    return num / den


def aggregated_attention_decode(
    q: jax.Array,                 # [H, d]
    k_cache: jax.Array,           # [S, Hkv, d]
    v_cache: jax.Array,           # [S, Hkv, d]
    bucket_of: jax.Array,         # [S] int32 in [0, K)
    mean_k: jax.Array,            # [K, Hkv, d]
    mean_v: jax.Array,            # [K, Hkv, d]
    counts: jax.Array,            # [K] int32
    refined: jax.Array,           # [K] bool — buckets attended exactly
    scale: float,
    valid_len: jax.Array | int | None = None,  # tokens written (<= S)
) -> jax.Array:
    """AccurateML two-stage decode attention oracle. Returns [H, d] float32.

    Refined buckets contribute their exact tokens; unrefined buckets
    contribute their centroid with logit  q·mean_k  and weight multiplied by
    ``count`` (all tokens retained in aggregate — the paper's differentiator
    vs. token-dropping sparsity).  GQA: query head h uses kv head
    h // (H // Hkv).
    """
    hq, d = q.shape
    s, hkv, _ = k_cache.shape
    kb = mean_k.shape[0]
    group = hq // hkv

    qf = q.astype(jnp.float32)
    tok_live = jnp.ones((s,), bool)
    if valid_len is not None:
        tok_live = jnp.arange(s) < valid_len
    out = []
    for h in range(hq):
        kvh = h // group
        logits_tok = (k_cache[:, kvh, :].astype(jnp.float32) @ qf[h]) * scale
        tok_refined = refined[bucket_of] & tok_live
        logits_tok = jnp.where(tok_refined, logits_tok, -jnp.inf)

        logits_cent = (mean_k[:, kvh, :].astype(jnp.float32) @ qf[h]) * scale
        cent_live = (~refined) & (counts > 0)
        logits_cent = jnp.where(cent_live, logits_cent, -jnp.inf)
        log_mult = jnp.where(
            cent_live, jnp.log(jnp.maximum(counts.astype(jnp.float32), 1.0)),
            0.0,
        )
        logits_cent = logits_cent + log_mult  # weight centroid by count

        all_logits = jnp.concatenate([logits_tok, logits_cent])
        # Clamp the running max to the finite NEG sentinel: with every
        # bucket empty (all logits -inf) the subtraction below would be
        # inf - inf = NaN before the isfinite mask discards it; clamped,
        # the all-empty cache yields exact zeros with no NaN transient.
        m = jnp.maximum(jnp.max(all_logits), NEG)
        w = jnp.exp(all_logits - m)
        w = jnp.where(jnp.isfinite(all_logits), w, 0.0)
        denom = jnp.maximum(jnp.sum(w), 1e-30)
        vals = jnp.concatenate(
            [
                v_cache[:, kvh, :].astype(jnp.float32),
                mean_v[:, kvh, :].astype(jnp.float32),
            ],
            axis=0,
        )
        out.append((w @ vals) / denom)
    return jnp.stack(out)


# Finite "minus infinity" for masked logits: exp(NEG - m) underflows to 0
# for any finite m, so merged-softmax arithmetic never produces a NaN from
# an inf - inf subtraction (the PR 9 "+inf spread, never 0/NaN" convention
# applied to attention logits).
NEG = -1.0e30


def agg_refine_attention(
    q: jax.Array,          # [B, Hkv, G, dk]
    k_slots: jax.Array,    # [B, K, C, Hkv, dk]
    v_slots: jax.Array,    # [B, K, C, Hkv, dv]
    counts: jax.Array,     # [B, K] int32 (total inserts incl. overflow)
    top_idx: jax.Array,    # [B, R] int32 — selected (refined) buckets
    use: jax.Array,        # [B, R] — 0 masks a selection slot (padding)
    scale: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stage-2 exact re-attention over the selected buckets' live slots.

    Returns the partial-softmax triple ``(m [B,Hkv,G], l [B,Hkv,G],
    acc [B,Hkv,G,dv])`` — running max, normalizer, and weighted value sum —
    so the caller merges it with the centroid pass via ``merge_partials``.
    The oracle gathers the [B,R,C,...] slot tensor; the Pallas kernel walks
    each selected bucket's rows straight from HBM (scalar-prefetch index
    map), mirroring ``refine_distances``.

    A selected-but-empty or masked bucket contributes ``m=NEG, l=0,
    acc=0`` — never a NaN, never attention weight.
    """
    b, hkv, g, dk = q.shape
    cap = k_slots.shape[2]
    dv = v_slots.shape[-1]
    idx = top_idx[:, :, None, None, None]
    k_sel = jnp.take_along_axis(
        k_slots, jnp.broadcast_to(
            idx, (b, top_idx.shape[1], cap, hkv, dk)
        ), axis=1,
    ).astype(jnp.float32)                                   # [B,R,C,Hkv,dk]
    v_sel = jnp.take_along_axis(
        v_slots, jnp.broadcast_to(
            idx, (b, top_idx.shape[1], cap, hkv, dv)
        ), axis=1,
    ).astype(jnp.float32)                                   # [B,R,C,Hkv,dv]
    cnt_sel = jnp.take_along_axis(counts, top_idx, axis=1)  # [B,R]
    live = (
        jnp.arange(cap)[None, None, :] < jnp.minimum(cnt_sel, cap)[:, :, None]
    ) & (cnt_sel > 0)[:, :, None] & (use != 0)[:, :, None]  # [B,R,C]

    qf = q.astype(jnp.float32)
    logits = jnp.einsum("bkgd,brckd->bkgrc", qf, k_sel) * scale
    logits = jnp.where(live[:, None, None], logits, NEG)
    flat = logits.reshape(b, hkv, g, -1)                    # [B,Hkv,G,R*C]
    m = jnp.max(flat, axis=-1)
    w = jnp.where(flat > NEG / 2, jnp.exp(flat - m[..., None]), 0.0)
    l = jnp.sum(w, axis=-1)
    vals = v_sel.transpose(0, 3, 1, 2, 4).reshape(b, hkv, -1, dv)
    acc = jnp.einsum("bkgt,bktd->bkgd", w, vals)
    return m, l, acc


def merge_partials(
    m1: jax.Array, l1: jax.Array, a1: jax.Array,
    m2: jax.Array, l2: jax.Array, a2: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Online-softmax merge of two partial triples (finite NEG sentinel:
    both-empty inputs merge to (NEG, 0, 0) with no NaN)."""
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m)
    w2 = jnp.exp(m2 - m)
    return m, l1 * w1 + l2 * w2, a1 * w1[..., None] + a2 * w2[..., None]


def segment_mean(
    data: jax.Array, ids: jax.Array, n_segments: int
) -> tuple[jax.Array, jax.Array]:
    """Bucket means + counts: [N,D],[N] -> ([K,D], [K])."""
    counts = jax.ops.segment_sum(
        jnp.ones(ids.shape, jnp.float32), ids, num_segments=n_segments
    )
    sums = jax.ops.segment_sum(
        data.astype(jnp.float32), ids, num_segments=n_segments
    )
    return sums / jnp.maximum(counts[:, None], 1.0), counts.astype(jnp.int32)
