"""Pallas TPU kernel: gather-by-page decode attention over the KV pool.

The serving engine's paged-KV pool (PR 3) made the page *map* device
resident, but decode still consumed densely materialized ``(B, S, KVH, hd)``
caches — every request's pages had to be gathered into a contiguous buffer
before attention could run.  This kernel reads the page *contents* in place:
each request walks its page-index vector and streams the pages it owns
through VMEM, one ``(page_size, KVH, hd)`` tile per grid step, with an
online-softmax accumulator carried across pages in scratch.

Layout and grid
---------------
* ``k_pages``/``v_pages``: ``(n_pages, page_size, KVH, hd)`` — the pool's
  page store.  A request's logical position ``t`` lives in page
  ``page_idx[b, t // page_size]`` at offset ``t % page_size``.
* grid = ``(B, P)`` with ``P = page_idx.shape[1]``: TPU grid steps run
  sequentially on a core, so the per-request softmax state (m/l/acc scratch)
  accumulates across the ``P`` inner steps and the output is emitted at the
  last page.
* ``page_idx`` and ``cache_len`` ride in as **scalar-prefetch** operands
  (``PrefetchScalarGridSpec``): the index map reads ``page_idx[b, p]`` to
  pick which page tile the next grid step DMAs — the gather happens in the
  block-fetch pipeline, not as a materialized ``take``.
* the walk is bounded by each row's live length: a row of ``cache_len``
  tokens holds ``n_live = ceil(cache_len / page_size)`` lanes.  Every lane
  past ``n_live`` names the block of the row's last live lane (lane 0 for
  an empty row), so consecutive dead steps name one block and the
  pipeline issues no DMA for them, and the kernel body skips their
  einsums, exps and accumulator updates.  A skipped lane would have added
  nothing (``pexp`` 0, ``corr`` 1), so the output is unchanged, and a row
  with ``cache_len == 0`` still emits zeros.  Inside the live lanes, slots
  past ``cache_len`` and ``-1`` lanes (clamped to page 0) stay masked out
  of the softmax.

The pure-jnp oracle (:func:`~repro.kernels.ref.paged_attn_ref`) mirrors the
page-walk order op for op so the CI smoke gate can require bit equality in
interpret mode, not just allclose.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def live_lanes(cache_len, page_size: int):
    """Lanes a row of ``cache_len`` tokens holds: ``ceil(cache_len /
    page_size)``; the walk does work on these lanes only."""
    return (cache_len + page_size - 1) // page_size


def _make_paged_attn_kernel(lanes_per_step: int, quantized: bool):
    """Kernel factory.  ``lanes_per_step`` (autotune knob): how many page
    lanes each grid step consumes — every lane is its own scalar-prefetched
    (1, ps, KVH, hd) block, so a step with k lanes has k independent DMAs
    in flight instead of one per step.  Each lane runs only if it is live
    (``p < n_live``), so a step may run some of its lanes and skip the
    rest when ``n_live`` is not a multiple of k.  ``quantized``: the page
    blocks are int8 and each is followed by its float32 per-page scale block
    (fetched through the SAME page-index map); dequantization is one cast
    + broadcast multiply at DMA time, inside VMEM — no fp32 copy of any
    page ever exists outside the kernel.  (The scales ride as (1, KVH, 1)
    blocks of an (n_pages, KVH, 1) view: a block must span the array's two
    minor dims or be (8, 128)-aligned, and (1, KVH) is neither.)"""
    per_lane = 4 if quantized else 2

    def kernel(pi_ref, cl_ref, q_ref, *refs):
        kv_refs = refs[:lanes_per_step * per_lane]
        o_ref, m_ref, l_ref, acc_ref = refs[-4:]
        b = pl.program_id(0)
        step = pl.program_id(1)
        n_steps = pl.num_programs(1)

        @pl.when(step == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        k0 = kv_refs[0]
        ps, kvh, hd = k0.shape[1], k0.shape[2], k0.shape[3]
        h = q_ref.shape[1]
        g = h // kvh
        scale = 1.0 / math.sqrt(hd)
        q = q_ref[0].astype(jnp.float32)                  # (H, hd)
        qh = q.reshape(kvh, g, hd)                        # heads grouped by
        clen = cl_ref[b]                                  # their kv head
        n_live = live_lanes(clen, ps)

        def attend(lane, p):
            page = pi_ref[b, p]
            # positions this page covers; invalid slots (past the request's
            # length, or an unallocated -1 page clamped to 0 by the index
            # map) are masked
            pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
            valid = (pos < clen) & (page >= 0)            # (1, ps)

            if quantized:
                k_ref, v_ref, ks_ref, vs_ref = lane
                k = k_ref[0].astype(jnp.float32) * ks_ref[0][None]
                v = v_ref[0].astype(jnp.float32) * vs_ref[0][None]
            else:
                k_ref, v_ref = lane
                k = k_ref[0].astype(jnp.float32)          # (ps, KVH, hd)
                v = v_ref[0].astype(jnp.float32)
            s = jnp.einsum("kgd,skd->kgs", qh, k,
                           preferred_element_type=jnp.float32) * scale
            s = s.reshape(h, ps)
            s = jnp.where(valid, s, -jnp.inf)

            m_prev = m_ref[...]                           # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pexp = jnp.where(valid, jnp.exp(s - m_safe), 0.0)   # (H, ps)
            corr = jnp.where(jnp.isfinite(m_prev),
                             jnp.exp(m_prev - m_safe), 0.0)
            l_ref[...] = l_ref[...] * corr \
                + jnp.sum(pexp, axis=1, keepdims=True)
            pv = jnp.einsum("kgs,skd->kgd", pexp.reshape(kvh, g, ps), v,
                            preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * corr + pv.reshape(h, hd)
            m_ref[...] = m_new

        for j in range(lanes_per_step):
            p = step * lanes_per_step + j
            pl.when(p < n_live)(functools.partial(
                attend, kv_refs[per_lane * j:per_lane * (j + 1)], p))

        @pl.when(step == n_steps - 1)
        def _emit():
            l = jnp.maximum(l_ref[...], 1e-20)            # fully-masked rows
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)  # (inactive
            #                                               slots) emit zeros
    return kernel


def _paged_attn_common(q, kv_operands, page_idx, cache_len, interpret,
                       lanes_per_step):
    """Shared call-path for the fp32 and quantized kernels.
    ``kv_operands`` is (k_pages, v_pages[, k_scale, v_scale])."""
    b, h, hd = q.shape
    _, ps, kvh, _ = kv_operands[0].shape
    assert h % kvh == 0, (h, kvh)
    lps = max(1, lanes_per_step)
    n_p = page_idx.shape[1]
    pad = -n_p % lps
    if pad:     # -1 padding lanes are exact no-ops in the online softmax
        page_idx = jnp.concatenate(
            [page_idx, jnp.full((b, pad), -1, page_idx.dtype)], axis=1)
        n_p += pad
    quantized = len(kv_operands) == 4
    if quantized:     # (n_pages, KVH) -> (n_pages, KVH, 1) scale view
        kv_operands = kv_operands[:2] + tuple(
            x[:, :, None] for x in kv_operands[2:])

    def page(j, bi, pi, idx_ref, cl_ref):
        # a dead lane names the row's last live lane's page: no new DMA
        last = jnp.maximum(live_lanes(cl_ref[bi], ps) - 1, 0)
        return jnp.maximum(idx_ref[bi, jnp.minimum(pi * lps + j, last)], 0)

    def kv_map(j):
        return lambda *a: (page(j, *a), 0, 0, 0)

    def scale_map(j):
        return lambda *a: (page(j, *a), 0, 0)

    in_specs = [pl.BlockSpec((1, h, hd), lambda bi, pi, idx, cl: (bi, 0, 0))]
    operands = []
    for j in range(lps):
        in_specs += [pl.BlockSpec((1, ps, kvh, hd), kv_map(j)),
                     pl.BlockSpec((1, ps, kvh, hd), kv_map(j))]
        operands += [kv_operands[0], kv_operands[1]]
        if quantized:
            in_specs += [pl.BlockSpec((1, kvh, 1), scale_map(j)),
                         pl.BlockSpec((1, kvh, 1), scale_map(j))]
            operands += [kv_operands[2], kv_operands[3]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # page_idx, cache_len
        grid=(b, n_p // lps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd), lambda bi, pi, idx, cl: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),    # running max
            pltpu.VMEM((h, 1), jnp.float32),    # running denominator
            pltpu.VMEM((h, hd), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        _make_paged_attn_kernel(lps, quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(page_idx.astype(jnp.int32), cache_len.astype(jnp.int32),
      q, *operands)


@functools.partial(jax.jit, static_argnames=("interpret", "lanes_per_step"))
def _paged_attn_call(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     page_idx: jax.Array, cache_len: jax.Array,
                     interpret: bool = False,
                     lanes_per_step: int = 1) -> jax.Array:
    """q: (B, H, hd); k/v_pages: (n_pages, ps, KVH, hd); page_idx: (B, P)
    int32 (-1 = unused lane); cache_len: (B,) valid lengths.  -> (B, H, hd).
    """
    return _paged_attn_common(q, (k_pages, v_pages), page_idx, cache_len,
                              interpret, lanes_per_step)


@functools.partial(jax.jit, static_argnames=("interpret", "lanes_per_step"))
def _paged_attn_quant_call(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, k_scale: jax.Array,
                           v_scale: jax.Array, page_idx: jax.Array,
                           cache_len: jax.Array, interpret: bool = False,
                           lanes_per_step: int = 1) -> jax.Array:
    """Quantized-pool variant: k/v_pages are (n_pages, ps, KVH, hd) int8
    and k/v_scale (n_pages, KVH) float32 per-page scales; both ride the
    same scalar-prefetched page-index path and pages dequantize in VMEM
    (``kernels.quant``).  Same shapes/masking otherwise."""
    return _paged_attn_common(q, (k_pages, v_pages, k_scale, v_scale),
                              page_idx, cache_len, interpret, lanes_per_step)
