"""Pallas TPU kernel: gather-free stage-2 exact distances (kNN refinement).

`accurateml_map` stage 2 used to materialize the gathered originals
``train_x[idx]`` as a [Q, B, D] tensor before a batched einsum — B·D bytes
of duplicated HBM traffic per query.  Here each selected row is copied
straight from the table in HBM into VMEM, so each selected original is
read exactly once and the gathered tensor never exists.

Row table: a row copy needs the table stored row-major, and a [N, D]
float32 table with D not a multiple of 128 is not: XLA stores it
feature-major (layout {0,1}), where one row is spread over every feature
tile.  Mosaic also refuses a manual copy of a slice narrower than the
table's lane tiling, or of one row of an (8, 128)-tiled second-minor
dimension.  So the kernel walks a *row table*, [N, 1, Dp] with the features
zero-padded to Dp, a multiple of 128 (`row_table`): XLA stores that shape
row-major, one row per (1, 128) tile, and each row is one leading-axis
index, which Mosaic copies whole.  A caller that refines the same table
many times (`KNNServable`) makes the row table once; a plain [N, D] table
is laid out on every call.  Of the two row-major forms, one-row copies
from the row table beat copying the aligned 8-row tile that holds each row
(from a [N, Dp] table) and picking its sublane in the kernel: on a TPU v5e,
4 x 184,000 random rows of 217 features took 13.8 ms against 19.7 ms.  The
walk is bound by the rate its copies are issued (about 19 ns a row), not
by bytes, so the tile form's 8x reads cost it (PERF.md).

Block walk: the grid is (Q, B_pad / R), R selected rows per step, R a
multiple of 128 chosen from the call's shapes (`rows_per_step`).  Each step
reads its R row indices from an SMEM block of the flattened selection,
starts one async copy per row into a [R, 1, Dp] VMEM buffer, and computes
the R distances as one [R, Dp] block: ``max(q² − 2 q·x + x², 0)`` in
float32, BIG where the slot is not valid (zero padding adds nothing to any
term).  The distances leave as one lane-dense (1, R) block of a
[Q, 1, B_pad] output.  The buffer is double-buffered across grid steps:
step s starts step s+1's copies (their indices come from a second SMEM
block, one block ahead) before it waits for its own, so one step's copies
overlap the compute of the step before.  One wait covers a step's R row
copies: a DMA semaphore counts the bytes that arrive.

The selection enters as blocks, not as a scalar-prefetch operand, so any
budget fits SMEM, the full refinement ``B = N`` included.  Padded selection
slots (``valid == 0``) emit the BIG sentinel, never a real distance — index
0's row is fetched (refinement_indices pads with 0) but its distance is
discarded in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_stream import BIG, pad_to_multiple

# Most rows per grid step, and the VMEM the two row buffers may take.
ROWS_MAX = 512
BUFFER_BYTES = 8 << 20


@jax.jit
def row_table(train_x: jax.Array) -> jax.Array:
    """[N, D] -> [N, 1, Dp], features zero-padded to a multiple of 128: the
    row-major layout the walk copies rows from.  A row table passes
    through unchanged."""
    if train_x.ndim == 3:
        return train_x
    return pad_to_multiple(train_x[:, None, :], 128, 2)


def rows_per_step(nb: int, dp: int) -> int:
    """R for a [Q, nb] selection of rows of dp (padded) features: a multiple
    of 128, at most ``ROWS_MAX``, two [R, 1, dp] float32 buffers within
    ``BUFFER_BYTES`` even if VMEM pads each row to 8 sublanes, and no more
    than one block for a small budget."""
    fit = max(128, BUFFER_BYTES // (2 * 8 * 4 * dp) // 128 * 128)
    return min(ROWS_MAX, fit, -(-nb // 128) * 128)


def _kernel(idx_ref, idx_next_ref, valid_ref, q_ref, x_hbm, out_ref,
            buf, sem):
    rows, dp = buf.shape[1], buf.shape[3]
    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    slot = step % 2

    def fetch(ids, s):
        def eight(i, carry):
            # Unrolled by hand: Mosaic unrolls a loop fully or not at all.
            for j in range(8):
                r = i * 8 + j
                pltpu.make_async_copy(
                    x_hbm.at[ids[0, r]], buf.at[s, r], sem.at[s]
                ).start()
            return carry

        jax.lax.fori_loop(0, rows // 8, eight, 0)

    @pl.when(step == 0)
    def _():
        fetch(idx_ref, 0)

    @pl.when(step + 1 < pl.num_programs(0) * pl.num_programs(1))
    def _():
        fetch(idx_next_ref, 1 - slot)

    # The R row copies into this slot, waited for as one copy of the slot.
    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()
    x = buf[slot].reshape(rows, dp).astype(jnp.float32)  # [R, Dp]
    q = q_ref[...].astype(jnp.float32)                   # [1, Dp]
    q2 = jnp.sum(q * q, axis=1, keepdims=True)           # [1, 1]
    x2 = jnp.sum(x * x, axis=1, keepdims=True).T         # [1, R]
    cross = jnp.sum(x * q, axis=1, keepdims=True).T      # [1, R]
    d = jnp.maximum(q2 - 2.0 * cross + x2, 0.0)
    out_ref[...] = jnp.where(valid_ref[...] != 0, d, BIG)


@functools.partial(jax.jit, static_argnames=("interpret",))
def refine_distances_pallas(
    queries: jax.Array, train_x: jax.Array,
    idx: jax.Array, valid: jax.Array,
    *, interpret: bool = False,
) -> jax.Array:
    """[Q,D] queries, [N,D] originals or their `row_table`, [Q,B] selection
    -> [Q,B] distances."""
    table = row_table(train_x)
    n, _, dp = table.shape
    nq, d = queries.shape
    nb = idx.shape[1]
    rows = rows_per_step(nb, dp)
    idx32 = jnp.clip(idx.astype(jnp.int32), 0, n - 1)
    idx32 = pad_to_multiple(idx32, rows, 1)
    nbp = idx32.shape[1]
    n_blocks = nbp // rows
    steps = nq * n_blocks
    idx32 = idx32.reshape(steps, 1, rows)
    valid3 = pad_to_multiple(valid.astype(jnp.int32), rows, 1)
    q3 = jnp.pad(queries, ((0, 0), (0, dp - d))).reshape(nq, 1, dp)

    def this_block(qi, bi):
        return qi * n_blocks + bi, 0, 0

    def next_block(qi, bi):
        return jnp.minimum(qi * n_blocks + bi + 1, steps - 1), 0, 0

    def lanes(qi, bi):
        return qi, 0, bi

    out = pl.pallas_call(
        _kernel,
        grid=(nq, n_blocks),
        in_specs=[
            pl.BlockSpec((None, 1, rows), this_block,
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, rows), next_block,
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, rows), lanes),
            pl.BlockSpec((None, 1, dp), lambda qi, bi: (qi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, 1, rows), lanes),
        out_shape=jax.ShapeDtypeStruct((nq, 1, nbp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, rows, 1, dp), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # Row copies run one grid step ahead, so the steps run in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(idx32, idx32, valid3.reshape(nq, 1, nbp), q3, table)
    return out.reshape(nq, nbp)[:, :nb]
