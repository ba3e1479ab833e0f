"""Pallas TPU kernel: gather-free stage-2 exact distances (kNN refinement).

`accurateml_map` stage 2 used to materialize the gathered originals
``train_x[idx]`` as a [Q, B, D] tensor before a batched einsum — B·D bytes
of duplicated HBM traffic per query.  Here the per-query refinement
selection (`RefinementSelection.point_idx`) is a *scalar-prefetch* operand
(`PrefetchScalarGridSpec`): the BlockSpec index map reads ``idx[q, b]`` and
DMAs that single row of ``train_x`` straight from HBM into VMEM, so each
selected original is read exactly once and the gathered tensor never
exists.

Block layout: Mosaic wants the last two block dims divisible by (8, 128)
or equal to the array's, so one-row blocks of a [N, D] table are refused.
The table is viewed as [N, 1, D] with block (None, 1, D) — a bitcast of a
row-major table — and each per-pair distance is written as its own (1, 1)
block of a [Q, B, 1, 1] output.  XLA stores a narrow table (D < 128)
column-major, so the call still pays one row-major copy of it (ROADMAP
Speed 6).

Scalar-prefetch operands live whole in SMEM (1 MiB on v5e), so a large
selection is walked in chunks of at most ``SMEM_PAIRS`` (query, slot)
pairs, one ``pallas_call`` per chunk under ``lax.map``.

Padded selection slots (``valid == 0``) emit the BIG sentinel, never a real
distance — index 0's row is fetched (refinement_indices pads with 0) but
its distance is discarded in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_stream import BIG, pad_to_multiple

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


def _kernel(idx_ref, valid_ref, q_ref, x_ref, out_ref):
    del idx_ref
    qi = pl.program_id(0)
    bi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)              # [1, D]
    x = x_ref[...].astype(jnp.float32)              # [1, D]
    q2 = jnp.sum(q * q, axis=1, keepdims=True)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    cross = jnp.sum(q * x, axis=1, keepdims=True)
    d = jnp.maximum(q2 - 2.0 * cross + x2, 0.0)     # [1, 1]
    out_ref[...] = jnp.where(valid_ref[qi, bi] != 0, d, BIG)


@functools.partial(jax.jit, static_argnames=("interpret",))
def refine_distances_pallas(
    queries: jax.Array, train_x: jax.Array,
    idx: jax.Array, valid: jax.Array,
    *, interpret: bool = False,
) -> jax.Array:
    """[Q,D] queries, [N,D] originals, [Q,B] selection -> [Q,B] distances."""
    nq, d = queries.shape
    nb = idx.shape[1]
    q3 = queries.reshape(nq, 1, d)
    x3 = train_x.reshape(train_x.shape[0], 1, d)
    chunk, n_chunks = chunk_selection(nq, nb)
    idx32 = jnp.clip(idx.astype(jnp.int32), 0, train_x.shape[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq, chunk),
        in_specs=[
            pl.BlockSpec(
                (None, 1, d), lambda qi, bi, idx_ref, val_ref: (qi, 0, 0)
            ),
            pl.BlockSpec(
                (None, 1, d),
                lambda qi, bi, idx_ref, val_ref: (idx_ref[qi, bi], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, 1, 1), lambda qi, bi, *_: (qi, bi, 0, 0)
        ),
    )
    call = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nq, chunk, 1, 1), jnp.float32),
        interpret=interpret,
    )

    def one_chunk(sel):
        i, v = sel
        return call(i, v, q3, x3).reshape(nq, chunk)

    out = jax.lax.map(one_chunk, (
        split_chunks(idx32, chunk, n_chunks),
        split_chunks(valid.astype(jnp.int32), chunk, n_chunks),
    ))                                              # [n_chunks, Q, chunk]
    return out.transpose(1, 0, 2).reshape(nq, n_chunks * chunk)[:, :nb]
