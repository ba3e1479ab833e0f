"""Pallas TPU kernel: gather-free CF stage-2 refinement.

The CF `accurateml_map` stage 2 used to gather three [Q, B, I] tensors
(`ratings[idx]`, `mask[idx]`, `centred[idx]`) to compute per-candidate
Pearson weights and their neighbourhood contributions.  This kernel walks
the per-query selection with scalar prefetch instead: grid (Q, B), each
step DMAs candidate ``idx[q, b]``'s centred-rating and mask rows straight
from HBM, forms the weight in registers, and accumulates

    num[q]  +=  w · centred_row        den[q]  +=  |w| · mask_row

into VMEM-resident [1, I] output blocks that flush once per query (the
output index map pins (q, 0) while b varies), so the [Q, B, I] intermediates
never touch HBM.

Every [rows, I] operand is viewed as [rows, 1, I] with block (None, 1, I)
and the per-pair weights form a [Q, B, 1, 1] output, the layout Mosaic
accepts for one-row blocks.  Large selections are walked in SMEM-sized
chunks; the running sums enter each chunk as its accumulators' initial
value, so the sums add in one pass's order.

``use`` gates candidates exactly like the einsum path: a non-used slot
contributes zero weight and zero sums (never NaN — the denominator is
clamped before the divide).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.topk_stream import pad_to_multiple

# (query, slot) pairs per chunk: two int32 scalar-prefetch operands of this
# many entries take 256 KiB of the 1 MiB SMEM.
SMEM_PAIRS = 32_768


def chunk_selection(nq: int, nb: int) -> tuple[int, int]:
    """(slots per chunk, number of chunks) for a [nq, nb] selection."""
    chunk = max(1, min(nb, SMEM_PAIRS // max(nq, 1)))
    return chunk, -(-nb // chunk)


def split_chunks(a: jax.Array, chunk: int, n_chunks: int) -> jax.Array:
    """[Q, B] -> [n_chunks, Q, chunk], zero-padding the slot axis."""
    a = pad_to_multiple(a, chunk, 1)
    return a.reshape(a.shape[0], n_chunks, chunk).transpose(1, 0, 2)


def _kernel(idx_ref, use_ref, ac_ref, am_ref, uc_ref, um_ref,
            num_in_ref, den_in_ref, w_ref, num_ref, den_ref, *, shrink):
    del idx_ref
    qi = pl.program_id(0)
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _():
        num_ref[...] = num_in_ref[...]
        den_ref[...] = den_in_ref[...]

    u = (use_ref[qi, bi] != 0).astype(jnp.float32)
    ac = ac_ref[...].astype(jnp.float32)            # [1, I] centred active
    am = am_ref[...].astype(jnp.float32)            # [1, I] active mask
    ref_c = uc_ref[...].astype(jnp.float32) * u     # [1, I] centred cand
    ref_m = um_ref[...].astype(jnp.float32) * u     # [1, I] cand mask

    w_num = jnp.sum(ac * ref_c, axis=1, keepdims=True)          # [1, 1]
    a_sq = jnp.sum(ac * ac * ref_m, axis=1, keepdims=True)
    u_sq = jnp.sum(am * ref_c * ref_c, axis=1, keepdims=True)
    co = jnp.sum(am * ref_m, axis=1, keepdims=True)
    w = w_num / jnp.sqrt(jnp.maximum(a_sq * u_sq, 1e-12))
    w = w * (co / (co + shrink))
    w = w * u

    w_ref[...] = w
    num_ref[...] = num_ref[...] + w * ref_c
    den_ref[...] = den_ref[...] + jnp.abs(w) * ref_m


def _center(r, m):
    """Centre rows by their masked mean (shares `ref._user_means` so the
    kernel wrapper and its oracle can never drift)."""
    return (r - ref._user_means(r, m)) * m


@functools.partial(jax.jit, static_argnames=("shrink", "interpret"))
def cf_refine_pallas(
    active: jax.Array, active_mask: jax.Array,
    ratings: jax.Array, mask: jax.Array,
    idx: jax.Array, use: jax.Array,
    *, shrink: float, interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-query exact CF refinement without the [Q,B,I] gathers.

    Returns (w_ref [Q,B], num_delta [Q,I], den_delta [Q,I]) matching the
    einsum oracle (`ref.cf_refine`) up to accumulation order.
    """
    n_items = active.shape[1]
    af = active.astype(jnp.float32)
    am = active_mask.astype(jnp.float32)
    ac = pad_to_multiple(_center(af, am), 128, 1)
    amp = pad_to_multiple(am, 128, 1)
    uc = pad_to_multiple(
        _center(ratings.astype(jnp.float32), mask.astype(jnp.float32)),
        128, 1,
    )
    ump = pad_to_multiple(mask.astype(jnp.float32), 128, 1)
    nq, ip = ac.shape
    nb = idx.shape[1]
    chunk, n_chunks = chunk_selection(nq, nb)
    idx32 = jnp.clip(idx.astype(jnp.int32), 0, ratings.shape[0] - 1)

    def row(qi, bi, i_ref, u_ref):
        return qi, 0, 0

    def cand(qi, bi, i_ref, u_ref):
        return i_ref[qi, bi], 0, 0

    def pair(qi, bi, i_ref, u_ref):
        return qi, bi, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq, chunk),
        in_specs=[
            pl.BlockSpec((None, 1, ip), row),
            pl.BlockSpec((None, 1, ip), row),
            pl.BlockSpec((None, 1, ip), cand),
            pl.BlockSpec((None, 1, ip), cand),
            pl.BlockSpec((None, 1, ip), row),
            pl.BlockSpec((None, 1, ip), row),
        ],
        out_specs=(
            pl.BlockSpec((None, None, 1, 1), pair),
            pl.BlockSpec((None, 1, ip), row),
            pl.BlockSpec((None, 1, ip), row),
        ),
    )
    call = pl.pallas_call(
        functools.partial(_kernel, shrink=shrink),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((nq, chunk, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1, ip), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1, ip), jnp.float32),
        ),
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
    )
    views = (
        ac.reshape(nq, 1, ip), amp.reshape(nq, 1, ip),
        uc.reshape(uc.shape[0], 1, ip), ump.reshape(ump.shape[0], 1, ip),
    )

    def one_chunk(sums, sel):
        # The running sums enter each chunk as the accumulators' initial
        # value, so a chunked walk adds in the same order as one pass.
        w, num, den = call(*sel, *views, *sums)
        return (num, den), w.reshape(nq, chunk)

    zeros = jnp.zeros((nq, 1, ip), jnp.float32)
    (num, den), w = jax.lax.scan(one_chunk, (zeros, zeros), (
        split_chunks(idx32, chunk, n_chunks),
        split_chunks(use.astype(jnp.int32), chunk, n_chunks),
    ))
    w = w.transpose(1, 0, 2).reshape(nq, n_chunks * chunk)[:, :nb]
    return w, num[:, 0, :n_items], den[:, 0, :n_items]
