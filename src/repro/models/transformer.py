"""Model assembly: heterogeneous block stacks, scan-over-units, train/prefill/
decode entry points for every assigned architecture family.

Stack layout (all archs):
  head blocks   — first_k_dense unrolled blocks (deepseek-v2 dense-FFN lead)
  scanned units — ceil-repeated cfg.pattern, parameters stacked [n_units, ...]
                  and iterated with lax.scan (compile-time O(1) in depth)
  tail blocks   — remainder blocks when n_layers isn't a multiple of the unit
  shared_attn   — single weight set applied at every 'shared_attn' slot
                  (zamba2's shared attention block)

Decode state mirrors the same layout so serve_step scans caches alongside
parameters.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import aggregated_kv, layers, mla, moe, ssm, xlstm

Params = dict[str, Any]

ATTN_KINDS = ("attn", "attn_local", "attn_global", "shared_attn")


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """How model code should use the mesh (None = single device)."""

    mesh: Any = None
    data_axes: tuple = ("data",)
    model_axis: str = "model"
    use_ep: bool = False             # expert-parallel MoE via shard_map
    seq_shard_moe: bool = True       # slice sequence over model axis in MoE
    pure_dp: bool = False            # model axis folded into data (xlstm)

    @property
    def active(self) -> bool:
        return self.mesh is not None


NO_PARALLEL = ParallelContext()


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def _block_has_moe(cfg, *, is_head: bool) -> bool:
    return cfg.n_experts > 0 and not is_head


def block_init(key, cfg, kind: str, *, dtype, is_head=False) -> Params:
    """Parameters of one block of the given kind."""
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": layers.rmsnorm_init(d, dtype=dtype)}
    if kind in ATTN_KINDS:
        if cfg.mla:
            p["attn"] = mla.mla_init(ks[0], cfg, dtype=dtype)
        else:
            p["attn"] = layers.attention_init(ks[0], cfg, dtype=dtype)
        if cfg.is_encoder_decoder:
            p["cross_norm"] = layers.rmsnorm_init(d, dtype=dtype)
            p["cross"] = layers.cross_attention_init(ks[2], cfg, dtype=dtype)
        if _block_has_moe(cfg, is_head=is_head):
            p["norm2"] = layers.rmsnorm_init(d, dtype=dtype)
            p["moe"] = moe.moe_init(ks[1], cfg, dtype=dtype)
        elif cfg.d_ff > 0:
            p["norm2"] = layers.rmsnorm_init(d, dtype=dtype)
            ff = cfg.d_ff
            if cfg.is_encoder_decoder:
                p["mlp"] = layers.gelu_mlp_init(ks[1], d, ff, dtype=dtype)
            else:
                p["mlp"] = layers.mlp_init(ks[1], d, ff, dtype=dtype)
    elif kind == "mamba":
        p["mixer"] = ssm.mamba_init(ks[0], cfg, dtype=dtype)
    elif kind == "mlstm":
        p["mixer"] = xlstm.mlstm_init(ks[0], cfg, dtype=dtype)
    elif kind == "slstm":
        p["mixer"] = xlstm.slstm_init(ks[0], cfg, dtype=dtype)
    else:
        raise ValueError(f"unknown block kind {kind}")
    return p


def _ffn_apply(p, x, cfg, parallel: ParallelContext, *, is_head=False):
    if _block_has_moe(cfg, is_head=is_head) and "moe" in p:
        h = layers.rmsnorm(x, p["norm2"], cfg.norm_eps)
        if parallel.active and parallel.use_ep:
            h = _moe_ep_sharded(p["moe"], h, cfg, parallel)
        else:
            h = moe.moe_dense(p["moe"], h, cfg)
        return x + h
    if "mlp" in p:
        h = layers.rmsnorm(x, p["norm2"], cfg.norm_eps)
        if cfg.is_encoder_decoder:
            h = layers.gelu_mlp(p["mlp"], h)
        else:
            h = layers.mlp(p["mlp"], h)
        return x + h
    return x


def _moe_ep_sharded(pm, x, cfg, parallel: ParallelContext):
    """shard_map wrapper around moe.moe_ep (DESIGN.md §4, EP over model)."""
    from jax.sharding import PartitionSpec as P

    mesh = parallel.mesh
    dax, max_ = parallel.data_axes, parallel.model_axis
    b, s, d = x.shape
    seq_shard = parallel.seq_shard_moe and (
        s % mesh.shape[max_] == 0 and s >= mesh.shape[max_]
    )

    param_specs = {
        "router": P(), "w_gate": P(max_, None, None),
        "w_up": P(max_, None, None), "w_down": P(max_, None, None),
    }
    if "shared" in pm:
        # shared experts are small; replicated over the model axis
        param_specs["shared"] = {"w_gate": P(), "w_up": P(), "w_down": P()}

    if seq_shard:
        x_spec = P(dax, max_, None)
        ep_fn = (
            moe.moe_ep_a2a if cfg.moe_dispatch == "all_to_all"
            else moe.moe_ep
        )

        def body(pl, xl):
            bl, sl, _ = xl.shape
            flat = xl.reshape(bl * sl, d)
            out = ep_fn(pl, flat, cfg, axis_name=max_)
            return out.reshape(bl, sl, d)

        return jax.shard_map(
            body, mesh=mesh, in_specs=(param_specs, x_spec),
            out_specs=x_spec, check_vma=False,
        )(pm, x)

    # decode / short-seq path: tokens replicated over the model axis, each
    # rank computes only its experts, contributions psum'd.  Tiny batches
    # (long-context decode, B=1) replicate over the data axes too.
    dsz = 1
    for a in (dax if isinstance(dax, tuple) else (dax,)):
        dsz *= mesh.shape[a]
    x_spec = P(dax, None, None) if b % dsz == 0 and b >= dsz \
        else P(None, None, None)

    def body_rep(pl, xl):
        bl, sl, _ = xl.shape
        flat = xl.reshape(bl * sl, d).astype(jnp.float32)
        n_ranks = jax.lax.axis_size(max_)
        rank = jax.lax.axis_index(max_)
        e_loc = cfg.n_experts // n_ranks
        out = moe.moe_apply_local(
            pl, flat, cfg, experts_slice=(rank * e_loc, e_loc)
        )
        out = jax.lax.psum(out, max_)
        if cfg.n_shared_experts > 0:
            shared_out = layers.mlp(pl["shared"], xl.reshape(bl * sl, d))
            out = out + shared_out.astype(jnp.float32)
        return out.reshape(bl, sl, d).astype(xl.dtype)

    return jax.shard_map(
        body_rep, mesh=mesh, in_specs=(param_specs, x_spec),
        out_specs=x_spec, check_vma=False,
    )(pm, x)


def block_apply(
    p: Params, x: jax.Array, cfg, kind: str, *, positions,
    parallel: ParallelContext = NO_PARALLEL, mrope_positions=None,
    memory=None, causal=True, is_head=False,
) -> jax.Array:
    """Full-sequence application of one block (train / prefill)."""
    h = layers.rmsnorm(x, p["norm1"], cfg.norm_eps)
    if kind in ATTN_KINDS:
        window = None
        if kind == "attn_local" and cfg.sliding_window > 0:
            window = cfg.sliding_window
        if cfg.mla:
            a = mla.mla_attention(p["attn"], h, cfg, positions=positions)
        else:
            a = layers.attention(
                p["attn"], h, cfg, positions=positions, causal=causal,
                window=window, mrope_positions=mrope_positions,
            )
        x = x + a
        if memory is not None and "cross" in p:
            c = layers.cross_attention(
                p["cross"],
                layers.rmsnorm(x, p["cross_norm"], cfg.norm_eps),
                memory, cfg,
            )
            x = x + c
        return _ffn_apply(p, x, cfg, parallel, is_head=is_head)
    if kind == "mamba":
        return x + ssm.mamba_block(p["mixer"], h, cfg)
    if kind == "mlstm":
        return x + xlstm.mlstm_block(p["mixer"], h, cfg)
    if kind == "slstm":
        return x + xlstm.slstm_block(p["mixer"], h, cfg)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# decode-time block (single token, stateful)
# ---------------------------------------------------------------------------

def init_block_cache(
    key, cfg, kind: str, *, batch: int, s_max: int, dtype,
) -> Any:
    if kind in ATTN_KINDS:
        use_agg = cfg.agg_kv and not (
            kind == "attn_local" and cfg.sliding_window > 0
        )
        agg_init = (
            aggregated_kv.init_bucket_major
            if cfg.agg_layout == "bucket_major"
            else aggregated_kv.init_cache
        )
        if use_agg and not cfg.mla:
            return agg_init(
                key, batch=batch, s_max=s_max, n_kv=cfg.n_kv_heads,
                dk=cfg.head_dim, compression=cfg.agg_compression,
                dtype=dtype,
            )
        if use_agg and cfg.mla:
            # latent-space aggregation: "keys" are [c_kv ; k_rope], MQA-like
            return agg_init(
                key, batch=batch, s_max=s_max, n_kv=1,
                dk=cfg.kv_lora_rank + cfg.rope_head_dim,
                dv=cfg.kv_lora_rank,
                compression=cfg.agg_compression, dtype=dtype,
            )
        if cfg.mla:
            return {
                "c": jnp.zeros((batch, s_max, cfg.kv_lora_rank), dtype),
                "kr": jnp.zeros((batch, s_max, cfg.rope_head_dim), dtype),
            }
        s_eff = s_max
        if kind == "attn_local" and cfg.sliding_window > 0:
            s_eff = min(s_max, cfg.sliding_window)
        return {
            "k": jnp.zeros(
                (batch, s_eff, cfg.n_kv_heads, cfg.head_dim), dtype
            ),
            "v": jnp.zeros(
                (batch, s_eff, cfg.n_kv_heads, cfg.head_dim), dtype
            ),
        }
    if kind == "mamba":
        d_in = cfg.ssm_expand * cfg.d_model
        gn2 = 2 * cfg.ssm_groups * cfg.ssm_state
        h = d_in // cfg.ssm_head_dim
        return {
            "conv": (
                jnp.zeros((batch, cfg.ssm_conv - 1, d_in), dtype),
                jnp.zeros((batch, cfg.ssm_conv - 1, gn2), dtype),
            ),
            "state": jnp.zeros(
                (batch, h, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32
            ),
        }
    if kind == "mlstm":
        return xlstm.mlstm_empty_state(batch, cfg)
    if kind == "slstm":
        return xlstm.slstm_empty_state(batch, cfg)
    raise ValueError(kind)


def _attn_decode_aggkv(p, h, cfg, cache, pos):
    """Aggregated-KV decode (the paper's technique; DESIGN.md §2.1)."""
    b = h.shape[0]
    bucket_major = cfg.agg_layout == "bucket_major"

    def attend(q_flat, cache, scale):
        if bucket_major:
            return aggregated_kv.decode_attend_bucket_major(
                q_flat, cache, refine_frac=cfg.agg_refine_frac, scale=scale,
            )
        return aggregated_kv.decode_attend(
            q_flat, cache, pos, refine_frac=cfg.agg_refine_frac,
            scale=scale,
        )

    def do_insert(cache, key_vec, val_vec):
        if bucket_major:
            return aggregated_kv.insert_bucket_major(cache, key_vec, val_vec)
        return aggregated_kv.insert(cache, key_vec, val_vec, pos)

    if cfg.mla:
        # build latent 'key' = [c ; k_rope], 'value' = c  (absorbed MQA form)
        c_new = layers.rmsnorm(h @ p["attn"]["w_dkv"],
                               p["attn"]["kv_norm"], cfg.norm_eps)
        kr_new = layers.apply_rope(
            (h @ p["attn"]["w_kr"]).reshape(b, 1, 1, cfg.rope_head_dim),
            pos[:, None], cfg.rope_theta,
        ).reshape(b, 1, cfg.rope_head_dim)
        key_vec = jnp.concatenate([c_new[:, 0], kr_new[:, 0]], -1)[:, None, :]
        cache = do_insert(cache, key_vec, c_new[:, 0][:, None, :])
        q_nope, q_rope = mla._mla_q(p["attn"], h, cfg, pos[:, None])
        r, hh = cfg.kv_lora_rank, cfg.n_heads
        w_uk = p["attn"]["w_uk"].reshape(r, hh, cfg.nope_head_dim)
        q_c = jnp.einsum(
            "bshd,rhd->bshr", q_nope.astype(jnp.float32),
            w_uk.astype(jnp.float32),
        )
        q_eff = jnp.concatenate(
            [q_c[:, 0], q_rope[:, 0].astype(jnp.float32)], axis=-1
        )                                                  # [B,H,r+dr]
        scale = 1.0 / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim)
        out_c = attend(q_eff, cache, scale)                # [B,H,r]
        w_uv = p["attn"]["w_uv"].reshape(r, hh, cfg.v_head_dim)
        out = jnp.einsum("bhr,rhd->bhd", out_c, w_uv.astype(jnp.float32))
        out = out.reshape(b, 1, hh * cfg.v_head_dim).astype(h.dtype)
        return out @ p["attn"]["wo"], cache

    q, k_new, v_new = layers._project_qkv(
        p["attn"], h, cfg, pos[:, None]
    )
    cache = do_insert(cache, k_new[:, 0], v_new[:, 0])
    out = attend(q[:, 0], cache, 1.0 / math.sqrt(cfg.head_dim))  # [B,H,hd]
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(h.dtype)
    return out @ p["attn"]["wo"], cache


def block_decode(
    p: Params, x: jax.Array, cfg, kind: str, cache, pos, *,
    parallel: ParallelContext = NO_PARALLEL, mrope_positions=None,
    memory_kv=None, is_head=False,
):
    """One decode step.  x: [B,1,d]; pos: [B].  Returns (x, new_cache)."""
    h = layers.rmsnorm(x, p["norm1"], cfg.norm_eps)
    if kind in ATTN_KINDS:
        use_agg = cfg.agg_kv and not (
            kind == "attn_local" and cfg.sliding_window > 0
        )
        if use_agg:
            a, cache = _attn_decode_aggkv(p, h, cfg, cache, pos)
        elif cfg.mla:
            a, c_new, kr_new = mla.mla_decode(
                p["attn"], h, cfg, cache_c=cache["c"],
                cache_kr=cache["kr"], pos=pos,
            )
            cache = {"c": c_new, "kr": kr_new}
        else:
            write_pos = None
            if kind == "attn_local" and cfg.sliding_window > 0:
                write_pos = pos % cache["k"].shape[1]  # ring buffer
            a, k_new, v_new = layers.attention_decode(
                p["attn"], h, cfg, cache_k=cache["k"], cache_v=cache["v"],
                pos=pos, write_pos=write_pos,
                mrope_positions=mrope_positions,
            )
            cache = {"k": k_new, "v": v_new}
        x = x + a
        if memory_kv is not None and "cross" in p:
            c = _cross_decode(
                p, layers.rmsnorm(x, p["cross_norm"], cfg.norm_eps),
                memory_kv, cfg,
            )
            x = x + c
        return _ffn_apply(x=x, p=p, cfg=cfg, parallel=parallel,
                          is_head=is_head), cache
    if kind == "mamba":
        a, conv, state = ssm.mamba_decode(
            p["mixer"], h, cfg, conv_cache=cache["conv"],
            ssm_state=cache["state"],
        )
        return x + a, {"conv": conv, "state": state}
    if kind == "mlstm":
        a, state = xlstm.mlstm_decode(p["mixer"], h, cfg, state=cache)
        return x + a, state
    if kind == "slstm":
        a, state = xlstm.slstm_decode(p["mixer"], h, cfg, state=cache)
        return x + a, state
    raise ValueError(kind)


def _cross_decode(p, h, memory_kv, cfg):
    """Cross-attention during decode against precomputed encoder K/V."""
    k_mem, v_mem = memory_kv                      # [B,T,H,hd] x2
    b = h.shape[0]
    hh, hd = cfg.n_heads, cfg.head_dim
    q = (h @ p["cross"]["wq"]).reshape(b, 1, hh, hd)
    logits = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32),
        k_mem.astype(jnp.float32),
    ) / math.sqrt(hd)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v_mem.astype(jnp.float32))
    return out.reshape(b, 1, hh * hd).astype(h.dtype) @ p["cross"]["wo"]
