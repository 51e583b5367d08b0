"""Pallas TPU kernel: streaming chunk-prefill attention over the KV pool.

PR 4's chunked prefill was the last dense detour on the paged data plane:
``make_paged_prefill_step`` scattered each chunk's K/V into the page store
and then *gathered every page back out densely* — a ``(B, lanes * ps, KVH,
hd)`` materialization per layer per tick — before attending.  Decode already
streamed pages through ``kernels.paged_attn``; this kernel closes the gap
for the S > 1 prefill path, so prompt chunks read the page store in place
too and the dense per-request KV buffer never exists anywhere.

Layout and grid
---------------
* ``q``: ``(B, S, H, hd)`` — one RIGHT-ALIGNED prompt chunk per row (row
  i's last ``new_lens[i]`` columns are real tokens; the leading columns are
  padding).  Column ``j``'s absolute position is ``cache_len - S + j``.
* ``k_pages``/``v_pages``: ``(L, n_pages, page_size, KVH * hd)`` — the
  pool's whole page store, shared by every request, in its one lane-dense
  layout (``kernels.paged_attn``); ``layer`` picks the layer.
* grid = ``(B, NQ, P)`` with ``NQ = S / block_q`` query blocks and ``P``
  page lanes: TPU grid steps run sequentially on a core, so the per-(row,
  q-block) softmax state (m/l/acc scratch) accumulates across the ``P``
  inner steps and the output block is emitted at the last page.
* queries are transposed head-major, ``(B, H, S, hd)``, around the call,
  so a KV head's grouped heads are one leading-dim slice of the block.  A
  token-major ``(bq, H, hd)`` block made Mosaic unroll the matmuls per
  (row, head): minutes of compile at 36 heads, against seconds now.
* each KV head reads its static lane slice of the page, ``[c * hd, (c +
  1) * hd)``: two plain matmuls per KV head, no reshape of the lanes.
* ``page_idx``/``cache_len``/``new_lens``/``layer`` ride in as
  **scalar-prefetch** operands (``PrefetchScalarGridSpec``): the index map
  reads ``page_idx[b, p]`` to pick which page tile the next grid step
  DMAs — the gather happens in the block-fetch pipeline, never as a
  materialized ``take``.
* the walk is bounded per (row, q-block): the block needs the lanes up to
  the smaller of the row's last page, ``ceil(cache_len / page_size)``, and
  its causal end, ``q_pos_max // page_size + 1`` with ``q_pos_max`` the
  position of its last column; a block of padding columns only (every
  block of a ``new_lens == 0`` row) needs none.  Every lane past that
  names the block of the last needed lane (lane 0 where none is needed),
  so consecutive dead steps name one block and the pipeline issues no
  DMA for them, and the kernel body skips their matmuls, exps and
  accumulator updates.  A skipped lane would have added nothing, so the
  output is unchanged; inside the needed lanes, ``-1`` lanes clamp to
  page 0 and stay masked out of the softmax.

Masking (all inside the kernel, per (q position, kv position) pair):
* kv position ``t`` is valid iff ``t < cache_len[b]`` and its lane holds a
  real page — the chunk attends to the WHOLE already-paged prefix plus its
  own freshly scattered K/V;
* causality at the right-aligned chunk boundary: ``t <= q_pos``;
* padding query columns (``j < S - new_lens[b]``, or rows past their
  length) are fully masked and emit zeros.

The pure-jnp oracle (:func:`~repro.kernels.ref.paged_chunk_attn_ref`)
mirrors the (row, q-block, page) walk op for op so the CI smoke gate can
require bit equality in interpret mode, not just allclose.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attn import NT, live_lanes


def _pick_block_q(s: int, limit: int = 32) -> int:
    """The q-block height: the largest divisor of ``s`` that is <= ``limit``
    and a multiple of 8 (the TPU sublane tile), or ``s`` itself when
    ``s <= limit``; failing both, the largest divisor <= ``limit`` (1 at
    worst, for prime widths — such a block only lowers in interpret
    mode)."""
    if s <= limit:
        return s
    for bq in range(limit - limit % 8, 0, -8):
        if s % bq == 0:
            return bq
    for bq in range(limit, 0, -1):
        if s % bq == 0:
            return bq
    raise AssertionError(s)          # unreachable: 1 divides everything


def _needed_lanes(clen, nl, qi, s: int, bq: int, ps: int):
    """Lanes q-block ``qi`` of a row attends to: up to the smaller of the
    row's last page and the block's causal end; none for a block made of
    padding columns only."""
    q_pos_max = clen - s + (qi + 1) * bq - 1
    n = jnp.minimum(live_lanes(clen, ps),
                    jnp.where(q_pos_max >= 0, q_pos_max // ps + 1, 0))
    return jnp.where((qi + 1) * bq > s - nl, n, 0)


def _make_chunk_attn_kernel(quantized: bool):
    """Kernel factory.  ``quantized``: the page blocks are int8, and the
    layer's float32 scales, ``(n_pages, KVH)``, sit whole in VMEM (one
    block for the whole grid, as in ``kernels.paged_attn``);
    dequantization is one cast + scalar multiply per KV head's lanes,
    inside VMEM — no fp32 copy of any page ever exists outside the
    kernel.

    Queries arrive head-major, ``(H, bq, hd)`` per block.  KV head ``c``
    is a static lane slice ``[c * hd, (c + 1) * hd)`` of the lane-dense
    page; the query-block rows of its ``g`` grouped heads stack into one
    ``(g * bq, hd)`` operand, so each KV head costs two plain matmuls."""

    def kernel(pi_ref, cl_ref, nl_ref, ly_ref, q_ref, *refs):
        if quantized:
            ks_ref, vs_ref, k_ref, v_ref = refs[:4]
        else:
            k_ref, v_ref = refs[:2]
        o_ref, m_ref, l_ref, acc_ref = refs[-4:]
        b = pl.program_id(0)
        qi = pl.program_id(1)
        p = pl.program_id(2)
        n_p = pl.num_programs(2)

        @pl.when(p == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        ps = k_ref.shape[0]
        h, bq, hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
        kvh = k_ref.shape[1] // hd
        n_q = pl.num_programs(1)
        s_total = bq * n_q
        g = h // kvh
        scale = 1.0 / math.sqrt(hd)

        clen = cl_ref[b]
        nl = nl_ref[b]

        @pl.when(p < _needed_lanes(clen, nl, qi, s_total, bq, ps))
        def _attend():
            page = pi_ref[b, p]
            # absolute positions: queries are the chunk's right-aligned
            # columns, keys are this page's slots; invalid slots / padding
            # columns masked
            col = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            q_pos = clen - s_total + col                   # (bq, 1)
            valid_q = (col >= s_total - nl) & (q_pos >= 0)
            t_pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
            valid = (t_pos < clen) & (page >= 0) & (t_pos <= q_pos) & valid_q
            if g > 1:                                      # (g * bq, ps)
                valid = jnp.concatenate([valid] * g, axis=0)

            for c in range(kvh):
                lanes = slice(c * hd, (c + 1) * hd)
                # (g, bq, hd) -> (g * bq, hd): the heads of KV head c
                qh = q_ref[0, c * g:(c + 1) * g].astype(
                    jnp.float32).reshape(g * bq, hd)
                k = k_ref[:, lanes].astype(jnp.float32)    # (ps, hd)
                v = v_ref[:, lanes].astype(jnp.float32)
                if quantized:         # the page's scale of KV head c
                    k = k * ks_ref[jnp.maximum(page, 0), c]
                    v = v * vs_ref[jnp.maximum(page, 0), c]
                s = jax.lax.dot_general(
                    qh, k, NT, preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid, s, -jnp.inf)          # (g * bq, ps)

                m_prev = m_ref[c]                          # (g * bq, 1)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                pexp = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
                corr = jnp.where(jnp.isfinite(m_prev),
                                 jnp.exp(m_prev - m_safe), 0.0)
                l_ref[c] = l_ref[c] * corr \
                    + jnp.sum(pexp, axis=1, keepdims=True)
                acc_ref[c] = acc_ref[c] * corr + jnp.dot(
                    pexp, v, preferred_element_type=jnp.float32)
                m_ref[c] = m_new

        @pl.when(p == n_p - 1)
        def _emit():
            l = jnp.maximum(l_ref[...], 1e-20)             # fully-masked rows
            o_ref[0] = (acc_ref[...] / l).reshape(h, bq, hd).astype(
                o_ref.dtype)                               # (padding) emit 0
    return kernel


def _chunk_attn_common(q, kv_operands, layer, page_idx, cache_len, new_lens,
                       interpret, block_q):
    """Shared call-path for the bf16 and quantized kernels.
    ``kv_operands`` is (k_pages, v_pages[, k_scale, v_scale])."""
    b, s, h, hd = q.shape
    _, n_pages, ps, lanes = kv_operands[0].shape
    n_p = page_idx.shape[1]
    kvh = lanes // hd
    assert lanes % hd == 0 and h % kvh == 0, (h, lanes, hd)
    g = h // kvh
    bq = block_q or _pick_block_q(s)
    assert s % bq == 0, (s, bq)
    n_q = s // bq
    quantized = len(kv_operands) == 4

    def block_map(bi, qi, p, idx_ref, cl_ref, nl_ref, ly_ref):
        # a dead lane names the last needed lane's page: no new DMA
        last = jnp.maximum(
            _needed_lanes(cl_ref[bi], nl_ref[bi], qi, s, bq, ps) - 1, 0)
        return (ly_ref[0], jnp.maximum(idx_ref[bi, jnp.minimum(p, last)], 0),
                0, 0)

    def q_map(bi, qi, p, *_):
        return (bi, 0, qi, 0)

    in_specs = [pl.BlockSpec((1, h, bq, hd), q_map)]
    operands = list(kv_operands[:2])
    if quantized:     # the layer's scales, whole: (n_pages, KVH)
        in_specs += [pl.BlockSpec((n_pages, kvh), lambda *_: (0, 0))] * 2
        operands = [x[layer] for x in kv_operands[2:]] + operands
    in_specs += [pl.BlockSpec((None, None, ps, lanes), block_map)] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,    # page_idx, cache_len, new_lens, layer
        grid=(b, n_q, n_p),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, bq, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, g * bq, 1), jnp.float32),   # running max
            pltpu.VMEM((kvh, g * bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((kvh, g * bq, hd), jnp.float32),  # output accumulator
        ],
    )
    # head-major queries: (B, S, H, hd) -> (B, H, S, hd) and back — an XLA
    # transpose of the chunk's activations, never of the page store
    out = pl.pallas_call(
        _make_chunk_attn_kernel(quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        interpret=interpret,
    )(page_idx.astype(jnp.int32), cache_len.astype(jnp.int32),
      new_lens.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.transpose(0, 2, 1, 3), *operands)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("interpret", "block_q"))
def _chunk_attn_call(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     layer: jax.Array, page_idx: jax.Array,
                     cache_len: jax.Array, new_lens: jax.Array,
                     interpret: bool = False,
                     block_q: int = 0) -> jax.Array:
    """q: (B, S, H, hd) right-aligned chunks; k/v_pages: (L, n_pages, ps,
    KVH * hd); layer: int32 scalar; page_idx: (B, P) int32 (-1 = unused
    lane); cache_len: (B,) total valid length AFTER the chunk; new_lens:
    (B,) valid trailing columns.  -> (B, S, H, hd) (padding columns
    zero)."""
    return _chunk_attn_common(q, (k_pages, v_pages), layer, page_idx,
                              cache_len, new_lens, interpret, block_q)


@functools.partial(jax.jit, static_argnames=("interpret", "block_q"))
def _chunk_attn_quant_call(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, k_scale: jax.Array,
                           v_scale: jax.Array, layer: jax.Array,
                           page_idx: jax.Array, cache_len: jax.Array,
                           new_lens: jax.Array, interpret: bool = False,
                           block_q: int = 0) -> jax.Array:
    """Quantized-pool variant: k/v_pages are (L, n_pages, ps, KVH * hd)
    int8 and k/v_scale (L, n_pages, KVH) float32 per-page scales; the
    pages ride the scalar-prefetched page-index path, the layer's scales
    sit in VMEM and pages dequantize there (``kernels.quant``).  Same
    shapes/masking otherwise."""
    return _chunk_attn_common(q, (k_pages, v_pages, k_scale, v_scale),
                              layer, page_idx, cache_len, new_lens,
                              interpret, block_q)
