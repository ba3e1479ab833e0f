"""Pallas TPU kernel: stage-2 exact re-attention over selected KV buckets.

The decode-side analogue of ``refine_distances``: stage 1 picks the
top-correlation buckets of the aggregated KV cache, stage 2 re-attends
exactly over those buckets' raw rows.  The per-sequence bucket selection
(``top_idx``) is a *scalar-prefetch* operand (``PrefetchScalarGridSpec``):
the BlockSpec index map reads ``top_idx[b, r]`` and DMAs that single
bucket's [C, Hkv, dk] slot rows straight from HBM into VMEM, so the
[B, R, C, ...] gathered tensor of the reference oracle never exists.

Grid is (B, R) with the selection axis minor; a per-sequence partial
softmax (running max / normalizer / weighted value sum) accumulates in
VMEM scratch across the R steps, and the last step writes the triple.
Masked or empty selections contribute the finite NEG sentinel — weight
zero, never a NaN (see ``ref.NEG``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG


def _kernel(idx_ref, use_ref, cnt_ref, q_ref, k_ref, v_ref,
            out_m, out_l, out_acc, m_s, l_s, acc_s,
            *, hkv, cap, scale):
    bi = pl.program_id(0)
    ri = pl.program_id(1)

    @pl.when(ri == 0)
    def _():
        m_s[...] = jnp.full_like(m_s[...], NEG)
        l_s[...] = jnp.zeros_like(l_s[...])
        acc_s[...] = jnp.zeros_like(acc_s[...])

    cnt = cnt_ref[bi, idx_ref[bi, ri]]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
    live = (
        (rows < jnp.minimum(cnt, cap)) & (cnt > 0) & (use_ref[bi, ri] != 0)
    )                                                       # [1, cap]

    # One KV head at a time: 2-D [G, dk] x [cap, dk] contractions, the
    # shapes Mosaic lowers onto the MXU without in-kernel reshapes.
    for h in range(hkv):
        q = q_ref[h].astype(jnp.float32)                    # [G, dk]
        k = k_ref[:, h, :].astype(jnp.float32)              # [cap, dk]
        v = v_ref[:, h, :].astype(jnp.float32)              # [cap, dv]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                           # [G, cap]
        logits = jnp.where(live, logits, NEG)
        bm = jnp.max(logits, axis=-1, keepdims=True)        # [G, 1]
        bw = jnp.where(logits > NEG / 2, jnp.exp(logits - bm), 0.0)
        bl = jnp.sum(bw, axis=-1, keepdims=True)            # [G, 1]
        bacc = jax.lax.dot_general(
            bw, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # [G, dv]

        m_old = m_s[h]
        m_new = jnp.maximum(m_old, bm)
        w_old = jnp.exp(m_old - m_new)
        w_b = jnp.exp(bm - m_new)
        m_s[h] = m_new
        l_s[h] = l_s[h] * w_old + bl * w_b
        acc_s[h] = acc_s[h] * w_old + bacc * w_b

    @pl.when(ri == pl.num_programs(1) - 1)
    def _():
        out_m[...] = m_s[...]
        out_l[...] = l_s[...]
        out_acc[...] = acc_s[...]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def agg_refine_attention_pallas(
    q: jax.Array,          # [B, Hkv, G, dk]
    k_slots: jax.Array,    # [B, K, C, Hkv, dk]
    v_slots: jax.Array,    # [B, K, C, Hkv, dv]
    counts: jax.Array,     # [B, K] int32
    top_idx: jax.Array,    # [B, R] int32
    use: jax.Array,        # [B, R] — 0 masks a selection slot
    *, scale: float, interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial-softmax triple over selected buckets; see ``ref.agg_refine_attention``."""
    b, hkv, group, dk = q.shape
    _, kb, cap, _, dv = v_slots.shape
    r = top_idx.shape[1]
    if r == 0:
        raise ValueError("empty selection: caller must skip R == 0")

    idx32 = jnp.clip(top_idx.astype(jnp.int32), 0, kb - 1)
    use32 = use.astype(jnp.int32)
    cnt32 = counts.astype(jnp.int32)

    def seq(bi, ri, idx_ref, use_ref, cnt_ref):
        return bi, 0, 0, 0

    def bucket(bi, ri, idx_ref, use_ref, cnt_ref):
        return bi, idx_ref[bi, ri], 0, 0, 0

    # Blocks keep the arrays' own last two dims ([G, dk], [Hkv, dk],
    # [G, 1], [G, dv]), so no operand is reshaped on the way in.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, r),
        in_specs=[
            pl.BlockSpec((None, hkv, group, dk), seq),
            pl.BlockSpec((None, None, cap, hkv, dk), bucket),
            pl.BlockSpec((None, None, cap, hkv, dv), bucket),
        ],
        out_specs=(
            pl.BlockSpec((None, hkv, group, 1), seq),
            pl.BlockSpec((None, hkv, group, 1), seq),
            pl.BlockSpec((None, hkv, group, dv), seq),
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, dv), jnp.float32),
        ],
    )
    out_m, out_l, out_acc = pl.pallas_call(
        functools.partial(_kernel, hkv=hkv, cap=cap, scale=scale),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, hkv, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, dv), jnp.float32),
        ),
        interpret=interpret,
    )(idx32, use32, cnt32, q, k_slots, v_slots)
    return out_m[..., 0], out_l[..., 0], out_acc
