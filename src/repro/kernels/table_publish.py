"""Pallas TPU kernels: batched visible-readers-table publish (CAS emulation).

The reader fast path CASes ``table[slot]: 0 -> lock_id`` (paper Listing 1
line 14).  The device-side lease table acquires many leases per engine step;
these kernels apply a *batch* of publish requests with the same semantics as
a sequence of CASes: the first request targeting a free slot wins, later
requests for the same slot (and requests for occupied slots) fail.

Two generations live here:

``_publish_call`` (legacy)
    Single grid step; the request loop is a ``fori_loop`` of dynamic
    single-element loads/stores — latency-bound, and the table block is
    copied input -> output on every call.

``_fused_publish_call`` (the device-BRAVO hot path)
    One request per ``fori_loop`` step, each step a whole-tile vector
    update: the request's slot becomes a one-hot mask over the (rows, 128)
    table tile, its occupancy and in-batch collisions (an earlier request
    that already claimed the slot) are masked sums reduced to SMEM scalars,
    and the winner's id lands with one ``where`` — exactly sequential-CAS
    semantics, duplicate slots included.  Per-request operands (slots, ids,
    bias) and the granted flags are SMEM scalars; no vector is narrower
    than the tile and nothing runs on the MXU.  The publish +
    rbias-recheck + conditional-undo of paper Listing 1 lines 14-22 are
    fused into the one kernel: the undo branch lowers to masking the store
    with ``rbias != 0``.  The table block is donated via
    ``input_output_aliases={0: 0}`` so the 16KB table is updated in place
    instead of copied per call; ``unconditional=True`` is the release path
    (store ``ids`` regardless of occupancy — with 0 ids that clears the
    slots).

``_fused_publish_multi_call`` (the multi-lock registry hot path)
    Same publish loop, but the scalar rbias operand becomes the registry's
    *per-lock bias vector* and each request carries a lock index: the
    kernel reads ``rbias[lock_idx]`` from SMEM inside the program, so one
    dispatch can publish leases for requests spanning many locks and the
    recheck/undo applies per request — a revoked lock's requests are
    undone while every other lock's requests land.  An unbiased request
    never attempts its CAS, so (matching the sequential semantics where a
    fast path not taken leaves the slot free) it does not shadow a later
    in-batch request for the same slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .table_scan import LANES


def _publish_kernel(table_ref, slots_ref, ids_ref, out_table_ref,
                    granted_ref, *, unconditional: bool):
    out_table_ref[...] = table_ref[...]
    m = slots_ref.shape[-1]

    def body(i, _):
        slot = slots_ref[0, i]
        row = slot // LANES
        col = slot % LANES
        cur = out_table_ref[pl.ds(row, 1), pl.ds(col, 1)][0, 0]
        val = ids_ref[0, i]
        if unconditional:
            ok = jnp.bool_(True)
        else:
            ok = cur == 0
        new = jnp.where(ok, val, cur)
        out_table_ref[pl.ds(row, 1), pl.ds(col, 1)] = new.reshape(1, 1)
        granted_ref[0, i] = ok.astype(jnp.int8)
        return 0

    jax.lax.fori_loop(0, m, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "unconditional"))
def _publish_call(table2d: jax.Array, slots: jax.Array, ids: jax.Array,
                  interpret: bool = False, unconditional: bool = False):
    rows, lanes = table2d.shape
    assert lanes == LANES, table2d.shape
    m = slots.shape[0]
    kern = functools.partial(_publish_kernel, unconditional=unconditional)
    table_out, granted = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), table2d.dtype),
            jax.ShapeDtypeStruct((1, m), jnp.int8),
        ],
        interpret=interpret,
    )(table2d, slots.reshape(1, m).astype(jnp.int32),
      ids.reshape(1, m).astype(table2d.dtype))
    return table_out, granted[0].astype(jnp.bool_)


# ---------------------------------------------------------------------------
# Fused, aliased, vectorized publish (the zero-sync fast path)
# ---------------------------------------------------------------------------


def _publish_loop(table_ref, out_table_ref, granted_ref, slots_ref, ids_ref,
                  attempts, *, check_free: bool):
    """Sequential-CAS semantics over the request batch, one request per
    loop step.  Per-request operands are SMEM scalars; the table is one
    (rows, LANES) VMEM tile and every vector is that tile's shape.

    Request ``i`` wins iff no EARLIER attempting request targeted its slot
    (``claimed``) and — unless ``check_free`` is off (release / forced
    store) — the slot was free in the incoming table.  ``attempts(i)`` is
    the scalar "this request tries its CAS" predicate (the rbias recheck
    of paper Listing 1 lines 14-22; a request whose fast path is off never
    CASes, so it neither wins nor shadows a later request)."""
    table = table_ref[...]                       # (rows, LANES) int32
    rows = table.shape[0]
    pos = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))

    def body(i, carry):
        new, claimed = carry
        hit = pos == slots_ref[i]                # (rows, LANES) one-hot
        taken = jnp.sum(jnp.where(hit, claimed, 0))
        ok = (taken == 0) & attempts(i)
        if check_free:
            ok = ok & (jnp.sum(jnp.where(hit, table, 0)) == 0)
        win = ok.astype(jnp.int32)
        new = jnp.where(hit & (win != 0), ids_ref[i], new)
        claimed = jnp.where(hit & attempts(i), 1, claimed)
        granted_ref[i] = win
        return new, claimed

    new, _ = jax.lax.fori_loop(
        0, slots_ref.shape[0], body, (table, jnp.zeros_like(table)))
    out_table_ref[...] = new


def _fused_publish_kernel(table_ref, rbias_ref, slots_ref, ids_ref,
                          out_table_ref, granted_ref, *,
                          unconditional: bool, check_rbias: bool):
    # publish + recheck-rbias + conditional undo (Listing 1 lines 14-22),
    # fused: an undone publish is a publish whose store never lands, so
    # the winners are masked with the bias flag read *in kernel*
    biased = rbias_ref[0] != 0

    def attempts(i):
        return biased if check_rbias else jnp.bool_(True)

    _publish_loop(table_ref, out_table_ref, granted_ref, slots_ref, ids_ref,
                  attempts, check_free=not unconditional)


def _publish_specs(n_scalar_operands: int) -> dict:
    """The table tile in VMEM (operand 0, aliased onto output 0); every
    per-request operand and the granted vector in SMEM."""
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return dict(
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)]
        + [smem] * n_scalar_operands,
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), smem],
        input_output_aliases={0: 0},     # table updated in place, no copy
    )


@functools.partial(jax.jit,
                   static_argnames=("interpret", "unconditional",
                                    "check_rbias"))
def _fused_publish_call(table2d: jax.Array, rbias: jax.Array,
                        slots: jax.Array, ids: jax.Array,
                        interpret: bool = False, unconditional: bool = False,
                        check_rbias: bool = True):
    """-> (new table [aliased onto the input buffer], granted bool (M,))."""
    rows, lanes = table2d.shape
    assert lanes == LANES, table2d.shape
    m = slots.shape[0]
    kern = functools.partial(_fused_publish_kernel,
                             unconditional=unconditional,
                             check_rbias=check_rbias)
    table_out, granted = pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), table2d.dtype),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        interpret=interpret,
        **_publish_specs(3),
    )(table2d, rbias.reshape(1).astype(jnp.int32), slots.astype(jnp.int32),
      ids.astype(table2d.dtype))
    return table_out, granted != 0


# ---------------------------------------------------------------------------
# Multi-lock fused publish: per-request rbias gathered by lock index
# ---------------------------------------------------------------------------


def _fused_publish_multi_kernel(table_ref, rbias_ref, slots_ref, lidx_ref,
                                ids_ref, out_table_ref, granted_ref):
    # per-request bias: rbias[lock_idx] gathered from SMEM — the registry's
    # per-lock recheck, in kernel (no host rbias read)
    def attempts(i):
        return rbias_ref[lidx_ref[i]] != 0

    _publish_loop(table_ref, out_table_ref, granted_ref, slots_ref, ids_ref,
                  attempts, check_free=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_publish_multi_call(table2d: jax.Array, rbias_vec: jax.Array,
                              slots: jax.Array, lock_idx: jax.Array,
                              ids: jax.Array, interpret: bool = False):
    """-> (new table [aliased onto the input buffer], granted bool (M,)).

    ``rbias_vec`` is the registry's (L,) int32 per-lock bias vector;
    ``lock_idx`` maps each request to its lock's bias lane."""
    rows, lanes = table2d.shape
    assert lanes == LANES, table2d.shape
    m = slots.shape[0]
    table_out, granted = pl.pallas_call(
        _fused_publish_multi_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), table2d.dtype),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        interpret=interpret,
        **_publish_specs(4),
    )(table2d, rbias_vec.astype(jnp.int32), slots.astype(jnp.int32),
      lock_idx.astype(jnp.int32), ids.astype(table2d.dtype))
    return table_out, granted != 0
