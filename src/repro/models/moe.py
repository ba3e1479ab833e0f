"""Mixture-of-Experts FFN with expert parallelism over the ``model`` mesh axis.

Two execution paths, same math:

  * ``moe_dense`` — reference path (single device / smoke tests): every
    expert computed for its capacity-selected tokens via plain gathers.
  * ``moe_ep``    — pod path (inside shard_map): experts sharded over the
    ``model`` axis; tokens all-gathered across the axis, each device runs
    its local experts over their selected tokens, contributions
    reduce-scattered back.  This is the paper-faithful *baseline* dispatch;
    the §Perf pass replaces the all-gather with an all-to-all send-buffer
    dispatch (see EXPERIMENTS.md).

Routing is token-choice top-k with per-expert capacity truncation
(capacity_factor), gates renormalized over the chosen experts
(DeepSeek-style).  Dropped tokens (over capacity) fall back to the shared
experts / residual path, matching standard "dropping" implementations.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers

Params = dict[str, Any]


def moe_init(key, cfg, *, dtype) -> Params:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p: Params = {
        "router": layers.dense_init(ks[0], d, e, dtype=jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, ff)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, ff)) * scale).astype(dtype),
        "w_down": (
            jax.random.normal(ks[3], (e, ff, d)) * (1.0 / math.sqrt(ff))
        ).astype(dtype),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = layers.mlp_init(
            ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts, dtype=dtype
        )
    return p


def _route(router_w, xf, top_k):
    """Router probabilities + normalized top-k gates.

    Returns (probs [T,E], gates [T,k], eidx [T,k]).
    """
    logits = xf.astype(jnp.float32) @ router_w         # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, top_k)          # [T, k]
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9
    )
    return probs, gates, eidx


def _select_for_expert(probs, gates, eidx, e, capacity):
    """Capacity-truncated token selection for expert ``e``.

    Returns (token_idx [C], gate [C], valid [C]) — the C highest-probability
    tokens that chose expert e in their top-k.
    """
    t = probs.shape[0]
    chose = jnp.any(eidx == e, axis=-1)                  # [T]
    gate_e = jnp.sum(jnp.where(eidx == e, gates, 0.0), axis=-1)
    score = jnp.where(chose, probs[:, e], -1.0)
    top_score, token_idx = jax.lax.top_k(score, capacity)
    valid = top_score > 0.0
    return token_idx, gate_e[token_idx] * valid, valid


def _expert_ffn(w_gate, w_up, w_down, x):
    """x: [C, d] through one expert's SwiGLU."""
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def moe_apply_local(
    p: Params, xf: jax.Array, cfg, *, experts_slice=None,
    axis_name: str | None = None,
) -> jax.Array:
    """Routed-experts computation over flat tokens xf [T, d].

    ``experts_slice``: (start, count) — which experts this caller owns
    (EP shard); None means all experts (dense path).  When ``axis_name`` is
    given, the caller is inside shard_map and contributions are psum'd by
    the caller via reduce_scatter.

    The expert loop follows cfg.scan_layers: fori_loop normally (compact
    HLO), unrolled in calibration mode so XLA's cost analysis counts every
    expert (while bodies are counted once by the analyzer).
    """
    t, d = xf.shape
    e_total = cfg.n_experts
    probs, gates, eidx = _route(p["router"], xf, cfg.moe_top_k)
    capacity = min(
        t,
        max(1, int(t * cfg.moe_top_k * cfg.capacity_factor / e_total)),
    )
    start, count = (0, e_total) if experts_slice is None else experts_slice

    out = jnp.zeros((t, d), jnp.float32)

    def body(i, out):
        e = start + i
        token_idx, gate, valid = _select_for_expert(
            probs, gates, eidx, e, capacity
        )
        x_e = xf[token_idx] * valid[:, None]
        w_g = jax.lax.dynamic_index_in_dim(p["w_gate"], i, 0, keepdims=False)
        w_u = jax.lax.dynamic_index_in_dim(p["w_up"], i, 0, keepdims=False)
        w_d = jax.lax.dynamic_index_in_dim(p["w_down"], i, 0, keepdims=False)
        y_e = _expert_ffn(w_g, w_u, w_d, x_e.astype(p["w_gate"].dtype))
        contrib = y_e.astype(jnp.float32) * gate[:, None]
        return out.at[token_idx].add(contrib)

    if getattr(cfg, "scan_layers", True):
        out = jax.lax.fori_loop(0, count, body, out)
    else:  # calibration: unrolled for exact cost analysis
        for i in range(count):
            out = body(i, out)
    return out


def moe_dense(p: Params, x: jax.Array, cfg) -> jax.Array:
    """Reference MoE (no mesh). x: [B, S, d]."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    out = moe_apply_local(p, xf, cfg)
    if cfg.n_shared_experts > 0:
        out = out + layers.mlp(p["shared"], xf).astype(jnp.float32)
    return out.reshape(b, s, d).astype(x.dtype)


def moe_ep_a2a(
    p: Params, x: jax.Array, cfg, *, axis_name: str = "model",
) -> jax.Array:
    """Expert-parallel MoE with all-to-all send-buffer dispatch (§Perf).

    Instead of all-gathering every token to every rank (baseline ``moe_ep``,
    ~2 x T_glob x d bytes/device), each rank packs per-destination buffers
    of only the tokens routed to that rank's experts and exchanges them
    with one all-to-all (~2 x T_loc x k x cf x d bytes/device) — the
    DeepSeek-style EP dispatch.  Buffers travel in bf16.
    """
    t_loc, d = x.shape
    n_ranks = jax.lax.axis_size(axis_name)
    e_total = cfg.n_experts
    e_loc = e_total // n_ranks
    probs, gates, eidx = _route(p["router"], x, cfg.moe_top_k)
    cap = min(
        t_loc,
        max(1, int(t_loc * cfg.moe_top_k * cfg.capacity_factor / e_total)),
    )

    token_idx, gate, valid = jax.vmap(
        lambda e: _select_for_expert(probs, gates, eidx, e, cap)
    )(jnp.arange(e_total))                       # [E,cap] x3

    send = (
        x[token_idx.reshape(-1)].reshape(e_total, cap, d)
        * valid[..., None]
    ).astype(jnp.bfloat16)
    send = send.reshape(n_ranks, e_loc * cap, d)
    recv = jax.lax.all_to_all(
        send, axis_name, split_axis=0, concat_axis=0, tiled=True
    )                                            # [n_ranks, e_loc*cap, d]

    # group received tokens by local expert: [e_loc, n_ranks*cap, d]
    grouped = (
        recv.reshape(n_ranks, e_loc, cap, d)
        .swapaxes(0, 1)
        .reshape(e_loc, n_ranks * cap, d)
    )
    up = jax.nn.silu(
        jnp.einsum("etd,edf->etf", grouped.astype(p["w_gate"].dtype),
                   p["w_gate"])
    ) * jnp.einsum("etd,edf->etf", grouped.astype(p["w_up"].dtype),
                   p["w_up"])
    y = jnp.einsum("etf,efd->etd", up, p["w_down"])  # [e_loc, n_ranks*cap, d]

    back = (
        y.reshape(e_loc, n_ranks, cap, d)
        .swapaxes(0, 1)
        .reshape(n_ranks, e_loc * cap, d)
        .astype(jnp.bfloat16)
    )
    ret = jax.lax.all_to_all(
        back, axis_name, split_axis=0, concat_axis=0, tiled=True
    )                                            # my tokens' expert outputs
    y_mine = ret.reshape(e_total, cap, d).astype(jnp.float32)

    out = jnp.zeros((t_loc, d), jnp.float32)
    out = out.at[token_idx.reshape(-1)].add(
        (y_mine * (gate * valid)[..., None]).reshape(-1, d)
    )
    if cfg.n_shared_experts > 0:
        out = out + layers.mlp(p["shared"], x).astype(jnp.float32)
    return out.astype(x.dtype)


def moe_ep(
    p: Params, x: jax.Array, cfg, *, axis_name: str = "model",
) -> jax.Array:
    """Expert-parallel MoE inside shard_map.

    Caller contract: x is this device's token slice [T_loc, d] (batch and
    sequence already sliced); expert weights in ``p`` are the LOCAL slice
    [E_loc, d, ff]; router weights are replicated.  Dispatch: all-gather
    tokens over ``axis_name``, compute local experts, reduce-scatter the
    contributions back (baseline collective schedule — see module docstring).
    """
    t_loc, d = x.shape
    n_ranks = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    e_loc = cfg.n_experts // n_ranks

    xf = x.astype(jnp.float32)
    x_all = jax.lax.all_gather(xf, axis_name, tiled=True)   # [T_glob, d]

    local = {
        "router": p["router"],
        "w_gate": p["w_gate"], "w_up": p["w_up"], "w_down": p["w_down"],
    }
    out_all = moe_apply_local(
        local, x_all, cfg, experts_slice=(rank * e_loc, e_loc),
        axis_name=axis_name,
    )                                                        # [T_glob, d]
    out = jax.lax.psum_scatter(
        out_all, axis_name, scatter_dimension=0, tiled=True
    )                                                        # [T_loc, d]
    if cfg.n_shared_experts > 0:
        out = out + layers.mlp(p["shared"], x).astype(jnp.float32)
    return out.astype(x.dtype)
