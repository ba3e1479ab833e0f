"""Smoke run of the served main path on a TPU: one chip, or the mesh map on four.

    python3 chip_smoke.py            # one chip: kernels, knn + cf, lm
    python3 chip_smoke.py --chips 4  # four chips: the kNN map on a data mesh

One process drives the chip.  With no option the phases run in order:

  kernels   every op of ``repro.kernels.ops`` dispatches to its compiled
            Pallas kernel, and each kernel agrees with its jnp oracle;
  knn + cf  one ``Server`` wired as ``repro.serve.demo`` wires it, over kNN at
            the paper's scale (2.3M x 64 float32 points) and CF at
            MovieLens-1M's shape (6,040 users x 3,706 items, ~4.5% dense):
            build aggregates, calibrate, prewarm, answer requests under a
            relaxed and a tight SLO, then check the two-stage map at full
            refinement against the exact map of the plain jnp reference;
  lm        ``LMServable`` over a bucket-major ``DecodeEngine`` behind a
            ``Server``: qwen3-8b at its published widths in bfloat16, depth
            cut to fit one chip; requests answered, then the aggregated
            decode's logits at refine_frac=1.0 checked against an
            exact-attention decode of the same model.

``--chips 4`` runs only the kNN exact map and the two-stage AccurateML map
on a 4-chip ``data`` mesh (``core.engine.MapReduce``), each compared with
the same job on one chip.

Earlier lines report sizes and times (informational, not measurements of
record).  The last line of standard output is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failure,
no TPU, or ``REPRO_FORCE_KERNELS`` set exits non-zero without that line.
Data and weights are random, made from fixed seeds.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# kNN at the paper's scale; CF at MovieLens-1M's shape; the serving batch.
# make_netflix_like caps each item's rating probability at 0.95, which
# trims the Zipf head: asking for 5.1% yields ~4.5% (~1.0M ratings, as in
# MovieLens-1M).
KNN_POINTS, KNN_D, N_CLASSES, KNN_K = 2_300_000, 64, 10, 5
CF_USERS, CF_ITEMS, CF_DENSITY = 6_040, 3_706, 0.051
BATCH = 4
# qwen3-8b at published widths, 8 of its 36 layers (~5.6 GB of bf16
# weights).  Bucket capacity 2 x 64 = 128 slots; a prompt plus its new
# tokens (128) fits one bucket, so refine_frac=1.0 attends every token
# exactly.
LM_LAYERS, LM_SLOTS, LM_S_MAX = 8, 4, 2048
LM_COMPRESSION, LM_PROMPT, LM_NEW = 64, 120, 8

# Distances are f32 sums of D=64 products: unit roundoff 2^-24 times 64
# terms is 3.8e-6 of the squared norms they are formed from; 1e-5 leaves
# headroom for the three sums in |q|^2 - 2 q.x + |x|^2.
KNN_TIE_RTOL = 1e-5
# CF predictions are ratings in [1, 5]; both sides form f32 sums over the
# 6,040 users in different orders (relative error ~ sqrt(6040) * 2^-24
# = 5e-6 of the rating scale), so 1e-3 of a star is far above rounding
# and far below any real difference.
CF_ATOL = 1e-3
# bfloat16 keeps 8 significant bits (relative rounding 2^-9).  At
# refine_frac=1.0 the aggregated and exact decodes attend the same tokens
# but sum them in different orders, so bf16 activations round differently
# layer by layer and the bf16 logits carry their own rounding.  Allow 3%
# of the position's largest logit — a few bf16 ulps through 8 layers —
# which a wrong mask or a dropped bucket (O(1) changes) exceeds.
LM_LOGIT_RTOL = 3e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def _gb(nbytes: float) -> str:
    return f"{nbytes / 1e9:.3f} GB"


def _memory(tag: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if stats:
        log(f"  memory after {tag}: in use {_gb(stats['bytes_in_use'])}, "
            f"peak {_gb(stats['peak_bytes_in_use'])}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

OPS = (
    "knn_distance", "distance_topk", "candidate_topk", "refine_distances",
    "cf_weights", "cf_refine", "agg_refine_attention",
)


# Kernel vs f32 oracle at highest precision, relative to the output scale.
# Selection is exact; VPU f32 arithmetic differs from the oracle only in
# summation order (1e-4).  Kernels with in-kernel matmuls may run f32
# products as bfloat16 passes on the MXU (relative rounding 2^-8 per
# product), so they get 1e-2.
KERNEL_RTOL = {
    "candidate_topk": 0.0, "refine_distances": 1e-4, "cf_refine": 1e-4,
    "knn_distance": 1e-2, "distance_topk": 1e-2, "cf_weights": 1e-2,
    "agg_refine_attention": 1e-2,
}


def check_dispatch() -> None:
    from repro.kernels import ops

    for op in OPS:
        path = ops.dispatch_path()
        log(f"  kernel {op}: {path}")
        if path != "pallas":
            raise RuntimeError(f"{op} dispatches to {path!r}, not 'pallas'")


def phase_kernels() -> None:
    """Each op once at a small shape, against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 12)
    q = jax.random.normal(ks[0], (8, 64))
    p = jax.random.normal(ks[1], (1000, 64))
    lab = jnp.arange(1000, dtype=jnp.int32)
    idx = jax.random.randint(ks[2], (8, 300), 0, 1000)
    valid = jax.random.uniform(ks[3], (8, 300)) < 0.8
    r = jnp.round(jax.random.uniform(ks[4], (208, 300), maxval=5.0))
    m = (jax.random.uniform(ks[5], (208, 300)) < 0.3).astype(jnp.float32)
    r = r * m
    b, kb, cap, hkv, g, dk = 2, 8, 16, 2, 4, 128
    qg = jax.random.normal(ks[6], (b, hkv, g, dk))
    kv = jax.random.normal(ks[7], (2, b, kb, cap, hkv, dk)).astype(
        jnp.bfloat16
    )
    counts = jax.random.randint(ks[8], (b, kb), 0, cap + 4)
    top = jax.random.randint(ks[9], (b, 5), 0, kb)
    use = jax.random.uniform(ks[10], (b, 5)) < 0.8
    lab2 = jnp.broadcast_to(lab, (8, 1000))
    with jax.default_matmul_precision("highest"):
        d_all = ref.knn_distance(q, p)
    # (kernel call, oracle call); oracles run at highest precision.
    cases = {
        "knn_distance": (
            lambda: ops.knn_distance(q, p), lambda: d_all,
        ),
        "distance_topk": (
            lambda: ops.distance_topk(q, p, lab, k=5)[0],
            lambda: ref.distance_topk(q, p, lab, k=5)[0],
        ),
        "candidate_topk": (
            lambda: ops.candidate_topk(d_all, lab2, k=5)[0],
            lambda: ref.candidate_topk(d_all, lab2, k=5)[0],
        ),
        "refine_distances": (
            lambda: jnp.where(valid, ops.refine_distances(q, p, idx, valid), 0),
            lambda: jnp.where(valid, ref.refine_distances(q, p, idx, valid), 0),
        ),
        "cf_weights": (
            lambda: ops.cf_weights(r[:8], m[:8], r[8:], m[8:]),
            lambda: ref.cf_weights(r[:8], m[:8], r[8:], m[8:]),
        ),
        "cf_refine": (
            lambda: ops.cf_refine(r[:8], m[:8], r[8:], m[8:], idx % 200,
                                  valid, shrink=8.0)[1],
            lambda: ref.cf_refine(r[:8], m[:8], r[8:], m[8:], idx % 200,
                                  valid, shrink=8.0)[1],
        ),
        "agg_refine_attention": (
            lambda: ops.agg_refine_attention(qg, kv[0], kv[1], counts, top,
                                             use, scale=dk ** -0.5)[2],
            lambda: ref.agg_refine_attention(qg, kv[0], kv[1], counts, top,
                                             use, dk ** -0.5)[2],
        ),
    }
    for op, (kernel, oracle) in cases.items():
        got = np.asarray(kernel())
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle())
        err = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want))) or 1.0
        log(f"  kernel {op}: max |kernel - oracle| = {err:.3g} "
            f"(oracle max {scale:.3g})")
        if not err <= KERNEL_RTOL[op] * scale:
            raise AssertionError(f"{op} disagrees with its oracle: {err}")


# ---------------------------------------------------------------------------
# knn + cf
# ---------------------------------------------------------------------------

def check_knn(d, lab, d_ref, lab_ref, tol) -> int:
    """Neighbour labels must match the reference's, up to distance ties.

    ``d_ref``/``lab_ref`` hold more candidates than ``d``/``lab``, so a
    neighbour tied with the k-th can be found on either side of the cut.
    Returns the number of positions settled by a tie.
    """
    import numpy as np

    ties = 0
    k = d.shape[1]
    for i in range(d.shape[0]):
        if not np.all(np.abs(d[i] - d_ref[i, :k]) <= tol[i]):
            raise AssertionError(
                f"query {i}: distances {d[i]} vs reference {d_ref[i, :k]}"
            )
        for j in range(k):
            if lab[i, j] == lab_ref[i, j]:
                continue
            tied = (np.abs(d_ref[i] - d[i, j]) <= tol[i]) & (
                lab_ref[i] == lab[i, j]
            )
            if not tied.any():
                raise AssertionError(
                    f"query {i} rank {j}: label {lab[i, j]} at distance "
                    f"{d[i, j]}, reference {lab_ref[i]} at {d_ref[i]}"
                )
            ties += 1
    return ties


def cf_exact_reference(ratings, mask, active, active_mask):
    """Exact user-based CF prediction in plain jnp (no kernels)."""
    import jax.numpy as jnp

    from repro.apps import cf as cf_lib
    from repro.kernels import ref

    w = ref.cf_weights(active, active_mask, ratings, mask)
    w = cf_lib.shrink_weights(w, active_mask @ mask.T)
    centred = (ratings - cf_lib.user_means(ratings, mask)) * mask
    return cf_lib.predict(w @ centred, jnp.abs(w) @ mask, active, active_mask)


def phase_knn_cf(
    *, knn_points=KNN_POINTS, knn_d=KNN_D, cf_users=CF_USERS,
    cf_items=CF_ITEMS, cf_density=CF_DENSITY, batch=BATCH,
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.serve.demo import build_demo_server, prepare_demo_server

    t0 = time.perf_counter()
    server, queries, active, active_mask = build_demo_server(
        knn_points=knn_points, knn_d=knn_d, cf_users=cf_users + 8,
        cf_items=cf_items, cf_density=cf_density, batch=batch,
    )
    knn, cf = server.servables["knn"], server.servables["cf"]
    jax.block_until_ready((knn.train_x, cf.ratings))
    log(f"  data: knn {knn.train_x.shape} float32 "
        f"({_gb(knn.train_x.nbytes)}), cf {cf.ratings.shape} at density "
        f"{float(jnp.mean(cf.mask)):.4f}, made in "
        f"{time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    slos = prepare_demo_server(server, batch=batch)
    log(f"  aggregates + calibrate + prewarm: "
        f"{time.perf_counter() - t0:.1f} s")
    ctl = server.controller
    for kind, servable in server.servables.items():
        prepared, _ = server.cache.get_or_build(
            servable, ctl.policy.compression_ratio
        )
        log(f"  {kind}: {servable.n_points} points, "
            f"{prepared.agg.means.shape[0]} aggregates, cost model "
            f"{ctl.models[kind]}, deadlines "
            + ", ".join(f"{c} {s * 1e3:.1f} ms" for c, s in slos[kind].items()))

    payloads = {
        "knn": [(queries[i],) for i in range(2 * batch)],
        "cf": [(active[i], active_mask[i]) for i in range(2 * batch)],
    }
    classes = {}
    for kind, rows in payloads.items():
        for i, payload in enumerate(rows):
            cls = "relaxed" if i < batch else "tight"
            rid = server.submit(kind, payload, slos[kind][cls])
            classes[rid] = (kind, cls)
    responses = server.drain()
    refined = set()
    for resp in responses:
        kind, cls = classes[resp.rid]
        if resp.stage1 is None:
            raise AssertionError(f"rid {resp.rid} has no stage-1 answer")
        if resp.refined is not None:
            refined.add(resp.rid)
        log(f"  {kind} {cls:7s} rid={resp.rid} eps={resp.eps_granted:.3f} "
            f"stage1 {resp.stage1_latency_s * 1e3:.1f} ms, total "
            f"{resp.total_latency_s * 1e3:.1f} ms, refined="
            f"{resp.refined is not None}, reexecuted={resp.reexecuted}, "
            f"deadline_met={resp.deadline_met}")
    missing = [r for r, (_, c) in classes.items()
               if c == "relaxed" and r not in refined]
    if missing:
        raise AssertionError(f"relaxed requests not refined: {missing}")

    # ---- two-stage map at full refinement == exact map ----
    policy_ratio = ctl.policy.compression_ratio
    prepared, _ = server.cache.get_or_build(knn, policy_ratio)
    q = queries[:batch]
    t0 = time.perf_counter()
    d, lab, _, _ = jax.block_until_ready(
        knn.run(prepared, (q,), refine_budget=knn.n_points)
    )
    log(f"  knn full refinement ({knn.n_points} points per query): first "
        f"call {time.perf_counter() - t0:.1f} s")
    with jax.default_matmul_precision("highest"):
        d_ref, lab_ref = jax.jit(
            partial(ref.distance_topk, k=knn.k + 4)
        )(q, knn.train_x, knn.train_y)
    x2_max = float(jnp.max(jnp.sum(jnp.square(knn.train_x), axis=1)))
    tol = KNN_TIE_RTOL * (np.sum(np.square(np.asarray(q)), axis=1) + x2_max)
    ties = check_knn(np.asarray(d), np.asarray(lab), np.asarray(d_ref),
                     np.asarray(lab_ref), tol)
    log(f"  knn: full-refinement neighbours match the exact reference "
        f"({ties} positions settled by distance ties within {tol.max():.3g})")

    prepared, _ = server.cache.get_or_build(cf, policy_ratio)
    pa = (active[:batch], active_mask[:batch])
    pred, _ = jax.block_until_ready(
        cf.run(prepared, pa, refine_budget=cf.n_points)
    )
    with jax.default_matmul_precision("highest"):
        pred_ref = jax.jit(cf_exact_reference)(cf.ratings, cf.mask, *pa)
    err = float(jnp.max(jnp.abs(pred - pred_ref)))
    log(f"  cf: full-refinement predictions vs exact reference: max |diff| "
        f"{err:.3g} stars (tolerance {CF_ATOL})")
    if not err <= CF_ATOL:
        raise AssertionError(f"cf predictions off by {err}")
    _memory("knn + cf")


# ---------------------------------------------------------------------------
# lm
# ---------------------------------------------------------------------------

def lm_config(n_layers=LM_LAYERS):
    from repro.configs import get_config

    return get_config("qwen3-8b").with_(
        n_layers=n_layers, agg_kv=True, agg_layout="bucket_major",
        agg_compression=LM_COMPRESSION,
    )


def exact_decode_logits(params, cfg, tokens, s_max):
    """Teacher-forced exact-attention decode: logits after every token.

    tokens [B, L] -> [B, L, vocab_padded] float32, each step through
    ``serve_step`` with a plain (non-aggregated) KV cache.
    """
    import jax
    import jax.numpy as jnp

    from repro.models import init_caches, serve_step

    exact = cfg.with_(agg_kv=False)
    bsz, length = tokens.shape
    caches = init_caches(jax.random.PRNGKey(7), exact, batch=bsz, s_max=s_max)

    @jax.jit
    def run(params, caches, tokens):
        def body(caches, t):
            pos = jnp.full((bsz,), t, jnp.int32)
            logits, caches = serve_step(
                params, caches, tokens[:, t][:, None], pos, exact
            )
            return caches, logits.astype(jnp.float32)

        _, logits = jax.lax.scan(body, caches, jnp.arange(length))
        return jnp.moveaxis(logits, 0, 1)

    return run(params, caches, tokens)


def phase_lm(
    cfg=None, *, slots=LM_SLOTS, s_max=LM_S_MAX, prompt_len=LM_PROMPT,
    new_tokens=LM_NEW,
) -> None:
    import jax
    import numpy as np

    from repro.configs.base import param_count
    from repro.core.budget import BudgetPolicy
    from repro.models import init_params
    from repro.serve.lm import DecodeEngine, LMServable
    from repro.serve.scheduler import ContinuousBatcher
    from repro.serve.server import Server

    cfg = cfg if cfg is not None else lm_config()
    log(f"  model: {cfg.name} d_model {cfg.d_model}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads} x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.dtype}; depth cut to {cfg.n_layers} layers "
        f"(~{param_count(cfg) / 1e9:.2f}B parameters)")
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    engine = DecodeEngine(
        params, cfg, max_slots=slots, s_max=s_max, key=jax.random.PRNGKey(7)
    )
    jax.block_until_ready(engine.state)
    log(f"  weights {_gb(nbytes)}, {engine.n_buckets} KV buckets x "
        f"{2 * cfg.agg_compression} slots per sequence, made in "
        f"{time.perf_counter() - t0:.1f} s")
    servable = LMServable(
        engine, prompt_len=prompt_len, max_new_tokens=new_tokens
    )
    policy = BudgetPolicy(eps_max=0.32, degrade_floor=0.004)
    server = Server(
        [servable], policy=policy,
        batcher=ContinuousBatcher(max_batch=slots, pad_sizes=(slots,)),
    )
    t0 = time.perf_counter()
    server.prewarm("lm", batch=slots, eps_values=[policy.eps_max])
    log(f"  prewarm (prefill + stage-1 and eps_max decode steps): "
        f"{time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(slots, prompt_len)
    ).astype(np.int32)
    for row in prompts:
        server.submit("lm", (row,), deadline_s=600.0)
    for resp in server.drain():
        ans = resp.refined if resp.refined is not None else resp.stage1
        if resp.stage1 is None or resp.refined is None:
            raise AssertionError(f"lm rid {resp.rid}: missing an answer")
        if not np.isfinite(ans["logits"]).all():
            raise AssertionError(f"lm rid {resp.rid}: non-finite logits")
        log(f"  lm rid={resp.rid} eps={resp.eps_granted:.3f} stage1 "
            f"{resp.stage1_latency_s:.2f} s, total "
            f"{resp.total_latency_s:.2f} s, tokens {ans['tokens'].tolist()}, "
            f"stage-1 vs refined token disagreement {resp.accuracy_proxy}")

    # ---- refine_frac=1.0 aggregated decode vs exact attention ----
    t0 = time.perf_counter()
    out = servable.run(engine, (prompts,), refine_budget=engine.n_buckets)
    log(f"  lm refine_frac=1.0 run: {time.perf_counter() - t0:.1f} s")
    seq = np.concatenate([prompts, out["tokens"][:, :-1]], axis=1)
    exact = np.asarray(exact_decode_logits(params, cfg, seq, s_max))
    exact = exact[:, prompt_len - 1:, :cfg.vocab_size]
    agg = out["logits"][:, :, :cfg.vocab_size]
    rel = np.max(np.abs(agg - exact), axis=-1) / np.max(
        np.abs(exact), axis=-1
    )                                                   # [B, new_tokens]
    log(f"  lm logits, refine_frac=1.0 vs exact attention: max |diff| / "
        f"max |logit| per position: worst {rel.max():.3g}, median "
        f"{np.median(rel):.3g} (tolerance {LM_LOGIT_RTOL})")
    if not rel.max() <= LM_LOGIT_RTOL:
        raise AssertionError(f"lm logits off by {rel.max()} of scale")
    _memory("lm")


# ---------------------------------------------------------------------------
# four chips: the kNN map on a data mesh
# ---------------------------------------------------------------------------

def phase_mesh(
    n_chips: int, *, knn_points=KNN_POINTS, knn_d=KNN_D, batch=BATCH,
    compression=20.0, eps=0.32,
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.apps import knn as knn_lib
    from repro.core import lsh as lsh_lib
    from repro.core.engine import CombineSpec, MapReduce, shard_leading
    from repro.core.refine import eps_to_budget
    from repro.data.synthetic import make_mfeat_like
    from repro.launch.mesh import make_host_mesh

    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    x, y = jax.block_until_ready(make_mfeat_like(
        key, n_points=knn_points + 64, n_features=knn_d,
        n_classes=N_CLASSES, modes_per_class=24, mode_scale=0.5,
    ))
    t_data = time.perf_counter() - t0
    train_x, train_y, q = x[64:], y[64:], x[:batch]
    n = knn_points // n_chips
    shards = [slice(s * n, (s + 1) * n) for s in range(n_chips)]
    cfg = lsh_lib.config_for_compression(n, compression)
    t0 = time.perf_counter()
    aggs = jax.block_until_ready([
        knn_lib.build_knn_aggregates(
            train_x[sl], train_y[sl],
            lsh_lib.init_lsh(jax.random.fold_in(key, s), knn_d, cfg),
            N_CLASSES,
        )
        for s, sl in enumerate(shards)
    ])
    budget = eps_to_budget(n, eps)
    k = KNN_K
    log(f"  {knn_points} points over {n_chips} shards of {n}, "
        f"{cfg.n_buckets} aggregates per shard, refine budget {budget}; "
        f"data made in {t_data:.1f} s, aggregates in "
        f"{time.perf_counter() - t0:.1f} s")

    def timed(tag, fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        log(f"  {tag}: first call {first:.2f} s, second "
            f"{time.perf_counter() - t0:.3f} s")
        return np.asarray(out[0]), np.asarray(out[1])

    # one chip: the whole job on device 0
    def one_chip_two_stage():
        d, l = zip(*(
            knn_lib.accurateml_map(
                train_x[sl], train_y[sl], agg, q, k=k, refine_budget=budget
            )
            for sl, agg in zip(shards, aggs)
        ))
        return knn_lib.merge_topk(jnp.stack(d), jnp.stack(l), k)

    # k + 4 candidates, so a neighbour tied with the k-th is visible; the
    # first k are exactly the k-candidate answer (same tie-break).
    exact_1 = timed("exact map, one chip", lambda: knn_lib.exact_map(
        train_x, train_y, q, k=k + 4
    ))
    two_1 = timed("two-stage map, one chip", one_chip_two_stage)

    # the mesh: one map shard per chip, all_gather shuffle, merged top-k
    mesh = make_host_mesh(shape=(n_chips,), axes=("data",))
    eng = MapReduce(mesh, axis="data")
    merge = CombineSpec(
        "all_gather", lambda g: knn_lib.merge_topk(g[0], g[1], k)
    )
    tx = shard_leading(mesh, "data", train_x)
    ty = shard_leading(mesh, "data", train_y)
    stacked = shard_leading(
        mesh, "data", jax.tree_util.tree_map(lambda *a: jnp.stack(a), *aggs)
    )
    exact_m = timed(f"exact map, {n_chips}-chip mesh", lambda: eng.run(
        lambda a, b: knn_lib.exact_map(a, b, q, k=k), merge, tx, ty
    ))
    two_m = timed(f"two-stage map, {n_chips}-chip mesh", lambda: eng.run(
        lambda a, b, g: knn_lib.accurateml_map(
            a, b, jax.tree_util.tree_map(lambda t: t[0], g), q, k=k,
            refine_budget=budget,
        ),
        merge, tx, ty, stacked,
    ))
    log(f"  shuffle bytes per mesh job: {eng.last_shuffle_bytes}")

    x2_max = float(jnp.max(jnp.sum(jnp.square(train_x), axis=1)))
    tol = KNN_TIE_RTOL * (np.sum(np.square(np.asarray(q)), axis=1) + x2_max)
    for tag, mesh_out, one_out in (
        ("exact", exact_m, exact_1), ("two-stage", two_m, two_1)
    ):
        ties = check_knn(mesh_out[0], mesh_out[1], *one_out, tol)
        same = np.array_equal(mesh_out[0], one_out[0][:, :k]) and (
            np.array_equal(mesh_out[1], one_out[1][:, :k])
        )
        log(f"  {tag} map: {n_chips}-chip mesh matches one chip "
            f"(bit-identical: {same}, ties: {ties})")


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the kNN map on a 4-chip data mesh",
    )
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_FORCE_KERNELS"):
        sys.exit("REPRO_FORCE_KERNELS is set: refusing to run the smoke "
                 "test off the compiled kernel path")

    from repro.launch.compile_cache import use_persistent_cache

    cache_dir = use_persistent_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU: JAX found {dev.platform} devices")
    if len(devices) < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} TPU chips, "
                 f"JAX found {len(devices)}")
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}, "
        f"jax {jax.__version__}, compile cache {cache_dir}")

    if args.chips == 4:
        phases = [("mesh", partial(phase_mesh, 4))]
    else:
        phases = [
            ("kernels", lambda: (check_dispatch(), phase_kernels())),
            ("knn + cf", phase_knn_cf),
            ("lm", phase_lm),
        ]
    t_all = time.perf_counter()
    for name, run in phases:
        log(f"phase {name}")
        t0 = time.perf_counter()
        run()
        gc.collect()
        log(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s")
    log(f"all phases ok in {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
