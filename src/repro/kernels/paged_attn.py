"""Pallas TPU kernel: gather-by-page decode attention over the KV pool.

The serving engine's paged-KV pool (PR 3) made the page *map* device
resident, but decode still consumed densely materialized ``(B, S, KVH, hd)``
caches — every request's pages had to be gathered into a contiguous buffer
before attention could run.  This kernel reads the page *contents* in place:
each request walks its page-index vector and streams the pages it owns
through VMEM, one lane-dense ``(page_size, KVH * hd)`` tile per grid step,
with an online-softmax accumulator carried across pages in scratch.

Layout and grid
---------------
* ``k_pages``/``v_pages``: ``(L, n_pages, page_size, KVH * hd)`` — the
  pool's whole page store, every layer, in the one layout it has at rest
  (row-major, the KV heads side by side on the lanes: no tile padding, so
  XLA never relays it out).  ``layer`` picks the layer; a request's logical
  position ``t`` lives in page ``page_idx[b, t // page_size]`` at offset
  ``t % page_size``, and KV head ``j`` on lanes ``[j * hd, (j + 1) * hd)``.
* grid = ``(B, P)`` with ``P = page_idx.shape[1]``: TPU grid steps run
  sequentially on a core, so the per-request softmax state (m/l/acc scratch)
  accumulates across the ``P`` inner steps and the output is emitted at the
  last page.
* ``page_idx``, ``cache_len`` and ``layer`` ride in as **scalar-prefetch**
  operands (``PrefetchScalarGridSpec``): the index map reads
  ``page_idx[b, p]`` to pick which page tile the next grid step DMAs — the
  gather happens in the block-fetch pipeline, not as a materialized
  ``take``.
* heads meet their KV lanes through two 0/1 matrices: ``sel`` (hd, KVH *
  hd) copies a head's hd values onto every KV head's lanes, ``own`` (H,
  KVH * hd) keeps the lanes of the head's own KV head.  At a row's first
  step ``qbd = where(own, q @ sel, 0)`` goes to scratch; each page then
  costs ``s = qbd @ k^T`` (H, ps) and ``p @ v`` (H, KVH * hd) into a
  lane-dense accumulator, and the emit takes ``where(own, acc, 0) @
  sel^T`` back to (H, hd).  Mosaic cannot split the lane dim to (KVH,
  hd); these matmuls need no such reshape and add nothing off a head's
  own lanes.
* the walk is bounded by each row's live length: a row of ``cache_len``
  tokens holds ``n_live = ceil(cache_len / page_size)`` lanes.  Every lane
  past ``n_live`` names the block of the row's last live lane (lane 0 for
  an empty row), so consecutive dead steps name one block and the
  pipeline issues no DMA for them, and the kernel body skips their
  matmuls, exps and accumulator updates.  A skipped lane would have added
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

# the 0/1 lane matmuls are exact copies only at full f32 precision
EXACT = jax.lax.Precision.HIGHEST
# contract the minor dims of both operands: a @ b^T
NT = (((1,), (1,)), ((), ()))


def live_lanes(cache_len, page_size: int):
    """Lanes a row of ``cache_len`` tokens holds: ``ceil(cache_len /
    page_size)``; the walk does work on these lanes only."""
    return (cache_len + page_size - 1) // page_size


def own_lanes(h: int, kvh: int, hd: int):
    """(h, kvh * hd) bool: lane ``j`` belongs to query head ``i``'s KV head,
    ``j // hd == i // g`` (``g = h // kvh``).  The lane maps are built from
    iotas inside the branch that needs them, so the kernels and the oracles
    make the same ones and an empty grid step makes none."""
    g = h // kvh
    j = jax.lax.broadcasted_iota(jnp.int32, (h, kvh * hd), 1)
    i = jax.lax.broadcasted_iota(jnp.int32, (h, kvh * hd), 0)
    return j // hd == i // g


def lane_sel(kvh: int, hd: int):
    """(hd, kvh * hd) float32: ``sel[d, j] = (j % hd == d)``."""
    j = jax.lax.broadcasted_iota(jnp.int32, (hd, kvh * hd), 1)
    d = jax.lax.broadcasted_iota(jnp.int32, (hd, kvh * hd), 0)
    return (j % hd == d).astype(jnp.float32)


def spread_q(q, kvh: int):
    """(H, hd) float32 queries -> (H, KVH * hd): each head's values on its
    own KV head's lanes, zero elsewhere."""
    h, hd = q.shape
    return jnp.where(own_lanes(h, kvh, hd),
                     jnp.dot(q, lane_sel(kvh, hd), precision=EXACT,
                             preferred_element_type=jnp.float32), 0.0)


def gather_out(acc, hd: int):
    """(H, KVH * hd) lane-dense accumulator -> (H, hd): each head's own
    lanes."""
    h, lanes = acc.shape
    kvh = lanes // hd
    return jax.lax.dot_general(
        jnp.where(own_lanes(h, kvh, hd), acc, 0.0), lane_sel(kvh, hd), NT,
        precision=EXACT, preferred_element_type=jnp.float32)


def head_scales(scale_row, h: int):
    """(1, KVH) per-KV-head page scales -> (H, 1), one per query head:
    ``sum(heads * scale_row)`` over the lanes, with ``heads[i, c] = (i //
    g == c)`` — one nonzero term per head, so the copy is exact."""
    kvh = scale_row.shape[1]
    i = jax.lax.broadcasted_iota(jnp.int32, (h, kvh), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (h, kvh), 1)
    heads = (i // (h // kvh) == c).astype(jnp.float32)
    return jnp.sum(heads * scale_row, axis=1, keepdims=True)


def _make_paged_attn_kernel(lanes_per_step: int, quantized: bool):
    """Kernel factory.  ``lanes_per_step`` (autotune knob): how many page
    lanes each grid step consumes — every lane is its own scalar-prefetched
    (ps, KVH * hd) block, so a step with k lanes has k independent DMAs in
    flight instead of one per step.  Each lane runs only if it is live
    (``p < n_live``), so a step may run some of its lanes and skip the
    rest when ``n_live`` is not a multiple of k.  ``quantized``: the page
    blocks are int8, and the layer's float32 scales, ``(n_pages, KVH)``,
    sit whole in VMEM (one block for the whole grid, fetched once per
    call; a block must span the array's two minor dims or be (8,
    128)-aligned, so one page's (1, KVH) row cannot be a block).  A
    head's scores and values touch only its own KV head's lanes, so that
    KV head's scale multiplies the scores and ``p @ v`` after the int8
    matmuls, inside VMEM — no fp32 copy of any page ever exists outside
    the kernel."""
    n_scales = 2 if quantized else 0

    def kernel(pi_ref, cl_ref, ly_ref, q_ref, *refs):
        scale_refs = refs[:n_scales]
        # (k, v) blocks, a pair per lane
        kv_refs = refs[n_scales:n_scales + 2 * lanes_per_step]
        o_ref, m_ref, l_ref, acc_ref, qbd_ref = refs[-5:]
        b = pl.program_id(0)
        step = pl.program_id(1)
        n_steps = pl.num_programs(1)

        k0 = kv_refs[0]
        ps, lanes = k0.shape
        h, hd = q_ref.shape[1], q_ref.shape[2]
        kvh = lanes // hd
        scale = 1.0 / math.sqrt(hd)

        @pl.when(step == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)
            qbd_ref[...] = spread_q(q_ref[0].astype(jnp.float32), kvh)

        clen = cl_ref[b]
        n_live = live_lanes(clen, ps)

        def attend(lane, p):
            page = pi_ref[b, p]
            # positions this page covers; invalid slots (past the request's
            # length, or an unallocated -1 page clamped to 0 by the index
            # map) are masked
            pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
            valid = (pos < clen) & (page >= 0)            # (1, ps)

            k = lane[0][...].astype(jnp.float32)          # (ps, KVH * hd)
            v = lane[1][...].astype(jnp.float32)
            s = jax.lax.dot_general(qbd_ref[...], k, NT,
                                    preferred_element_type=jnp.float32)
            if quantized:
                row = pl.ds(jnp.maximum(page, 0), 1)
                s = s * head_scales(scale_refs[0][row, :], h)
            s = jnp.where(valid, s * scale, -jnp.inf)     # (H, ps)

            m_prev = m_ref[...]                           # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pexp = jnp.where(valid, jnp.exp(s - m_safe), 0.0)   # (H, ps)
            corr = jnp.where(jnp.isfinite(m_prev),
                             jnp.exp(m_prev - m_safe), 0.0)
            l_ref[...] = l_ref[...] * corr \
                + jnp.sum(pexp, axis=1, keepdims=True)
            pv = jnp.dot(pexp, v, preferred_element_type=jnp.float32)
            if quantized:
                pv = pv * head_scales(scale_refs[1][row, :], h)
            acc_ref[...] = acc_ref[...] * corr + pv       # (H, KVH * hd)
            m_ref[...] = m_new

        for j in range(lanes_per_step):
            p = step * lanes_per_step + j
            pl.when(p < n_live)(functools.partial(
                attend, kv_refs[2 * j:2 * j + 2], p))

        @pl.when(step == n_steps - 1)
        def _emit():
            l = jnp.maximum(l_ref[...], 1e-20)            # fully-masked rows
            o_ref[0] = gather_out(acc_ref[...] / l, hd).astype(
                o_ref.dtype)                  # (inactive slots) emit zeros
    return kernel


def _paged_attn_common(q, kv_operands, layer, page_idx, cache_len,
                       interpret, lanes_per_step):
    """Shared call-path for the bf16 and quantized kernels.
    ``kv_operands`` is (k_pages, v_pages[, k_scale, v_scale])."""
    b, h, hd = q.shape
    _, n_pages, ps, lanes = kv_operands[0].shape
    assert lanes % hd == 0 and h % (lanes // hd) == 0, (h, lanes, hd)
    lps = max(1, lanes_per_step)
    n_p = page_idx.shape[1]
    pad = -n_p % lps
    if pad:     # -1 padding lanes are exact no-ops in the online softmax
        page_idx = jnp.concatenate(
            [page_idx, jnp.full((b, pad), -1, page_idx.dtype)], axis=1)
        n_p += pad
    quantized = len(kv_operands) == 4

    def page(j, bi, pi, idx_ref, cl_ref):
        # a dead lane names the row's last live lane's page: no new DMA
        last = jnp.maximum(live_lanes(cl_ref[bi], ps) - 1, 0)
        return jnp.maximum(idx_ref[bi, jnp.minimum(pi * lps + j, last)], 0)

    def block_map(j):
        return lambda bi, pi, idx_ref, cl_ref, ly_ref: (
            ly_ref[0], page(j, bi, pi, idx_ref, cl_ref), 0, 0)

    in_specs = [pl.BlockSpec((1, h, hd), lambda bi, pi, *_: (bi, 0, 0))]
    operands = []
    if quantized:     # the layer's scales, whole: (n_pages, KVH)
        kvh = lanes // hd
        in_specs += [pl.BlockSpec((n_pages, kvh), lambda *_: (0, 0))] * 2
        operands += [x[layer] for x in kv_operands[2:]]
    for j in range(lps):
        in_specs += [pl.BlockSpec((None, None, ps, lanes), block_map(j))] * 2
        operands += [kv_operands[0], kv_operands[1]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # page_idx, cache_len, layer
        grid=(b, n_p // lps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd), lambda bi, pi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),      # running max
            pltpu.VMEM((h, 1), jnp.float32),      # running denominator
            pltpu.VMEM((h, lanes), jnp.float32),  # output accumulator
            pltpu.VMEM((h, lanes), jnp.float32),  # the row's spread queries
        ],
    )
    return pl.pallas_call(
        _make_paged_attn_kernel(lps, quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(page_idx.astype(jnp.int32), cache_len.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, *operands)


@functools.partial(jax.jit, static_argnames=("interpret", "lanes_per_step"))
def _paged_attn_call(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     layer: jax.Array, page_idx: jax.Array,
                     cache_len: jax.Array, interpret: bool = False,
                     lanes_per_step: int = 1) -> jax.Array:
    """q: (B, H, hd); k/v_pages: (L, n_pages, ps, KVH * hd); layer: int32
    scalar; page_idx: (B, P) int32 (-1 = unused lane); cache_len: (B,)
    valid lengths.  -> (B, H, hd)."""
    return _paged_attn_common(q, (k_pages, v_pages), layer, page_idx,
                              cache_len, interpret, lanes_per_step)


@functools.partial(jax.jit, static_argnames=("interpret", "lanes_per_step"))
def _paged_attn_quant_call(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, k_scale: jax.Array,
                           v_scale: jax.Array, layer: jax.Array,
                           page_idx: jax.Array, cache_len: jax.Array,
                           interpret: bool = False,
                           lanes_per_step: int = 1) -> jax.Array:
    """Quantized-pool variant: k/v_pages are (L, n_pages, ps, KVH * hd)
    int8 and k/v_scale (L, n_pages, KVH) float32 per-page scales; the
    pages ride the scalar-prefetched page-index path, the layer's scales
    sit in VMEM and apply there (``kernels.quant``).  Same shapes/masking
    otherwise."""
    return _paged_attn_common(q, (k_pages, v_pages, k_scale, v_scale),
                              layer, page_idx, cache_len, interpret,
                              lanes_per_step)
