"""Pallas TPU kernel: visible-readers-table revocation scan.

The BRAVO writer's revocation step scans the whole visible-readers table for
slots publishing its lock (paper Listing 1 lines 42-44).  The paper's future
work proposes accelerating this scan with SIMD (AVX) and non-polluting
loads; on TPU the idiomatic equivalent is a VPU-vectorized scan that streams
the table through VMEM tiles (never resident in caches the MXU path cares
about).

Layout: the table is shaped (rows, 128) int32 — 128 lanes per VPU register
row; block = (BLOCK_ROWS, 128) tiles.  Outputs: a per-slot match mask (int8)
and the total match count (accumulated across sequential grid steps, as TPU
grid iterations execute in order on a core).  Lock values arrive as
scalar-prefetch operands and counts are SMEM scalars: Mosaic keeps scalars
in SMEM and vectors in VMEM tiles, never a scalar store into a VMEM tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 8


def _scan_kernel(lock_ref, table_ref, mask_ref, count_ref):
    """``lock_ref`` (1,) and ``count_ref`` (1,) live in SMEM: the count is
    a scalar accumulated across the sequential grid steps, and Mosaic
    stores scalars only to SMEM."""
    m = table_ref[...] == lock_ref[0]          # (BLOCK_ROWS, 128)
    mask_ref[...] = m.astype(jnp.int8)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        count_ref[0] = 0

    count_ref[0] += jnp.sum(m.astype(jnp.int32))


def _lock_operand(lock_id: jax.Array, dtype) -> jax.Array:
    """The lock value as a (1,) scalar-prefetch operand (SMEM)."""
    return jnp.reshape(lock_id.astype(dtype), (1,))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(table2d: jax.Array, lock_id: jax.Array,
               interpret: bool = False):
    rows, lanes = table2d.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, table2d.shape
    mask, count = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // BLOCK_ROWS,),
            in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i, lk: (i, 0))],
            out_specs=[
                pl.BlockSpec((BLOCK_ROWS, LANES), lambda i, lk: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=interpret,
    )(_lock_operand(lock_id, table2d.dtype), table2d)
    return mask, count[0]


def _poll_kernel(lock_ref, table_ref, count_ref):
    """Early-exit variant: a drain-polling writer only needs zero/nonzero.

    TPU grid steps run sequentially on a core, so once an earlier block has
    found a match every later step skips its compare entirely — the common
    "table still held" poll touches only a prefix of the table.  The count
    returned is exact when zero and a lower bound (>= 1) otherwise.
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        count_ref[0] = 0

    @pl.when(count_ref[0] == 0)
    def _scan():
        blk = table_ref[...]
        count_ref[0] = jnp.sum((blk == lock_ref[0]).astype(jnp.int32))


def _multi_poll_kernel(locks_ref, table_ref, counts_ref):
    """Per-lock hold counts for a *vector* of lock values, one table pass.

    The registry drains several locks at once (e.g. freeing a striped KV
    pool) and must poll each lock without disturbing any other lock's bias:
    polling never touches rbias at all, and one streamed pass produces all
    K counts instead of K scans.  Each lock value is an SMEM scalar compared
    against the whole (BLOCK_ROWS, 128) tile, so every vector stays the
    tile's own rank-2 shape; the K counts accumulate as SMEM scalars.
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        for j in range(counts_ref.shape[0]):
            counts_ref[j] = 0

    blk = table_ref[...]                       # (BLOCK_ROWS, 128)

    def body(j, carry):
        counts_ref[j] += jnp.sum((blk == locks_ref[j]).astype(jnp.int32))
        return carry

    jax.lax.fori_loop(0, counts_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _multi_poll_call(table2d: jax.Array, lock_ids: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """-> (K,) int32 exact hold counts, one count per entry of ``lock_ids``."""
    rows, lanes = table2d.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, table2d.shape
    k = lock_ids.shape[0]
    return pl.pallas_call(
        _multi_poll_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // BLOCK_ROWS,),
            in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i, lk: (i, 0))],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=jax.ShapeDtypeStruct((k,), jnp.int32),
        interpret=interpret,
    )(lock_ids.astype(table2d.dtype), table2d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _poll_call(table2d: jax.Array, lock_id: jax.Array,
               interpret: bool = False) -> jax.Array:
    rows, lanes = table2d.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, table2d.shape
    count = pl.pallas_call(
        _poll_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // BLOCK_ROWS,),
            in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i, lk: (i, 0))],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        interpret=interpret,
    )(_lock_operand(lock_id, table2d.dtype), table2d)
    return count[0]
