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
* ``k_pages``/``v_pages``: ``(n_pages, page_size, KVH, hd)`` — the pool's
  page store, shared by every request.
* grid = ``(B, NQ, P)`` with ``NQ = S / block_q`` query blocks and ``P``
  page lanes: TPU grid steps run sequentially on a core, so the per-(row,
  q-block) softmax state (m/l/acc scratch) accumulates across the ``P``
  inner steps and the output block is emitted at the last page.
* queries are transposed head-major, ``(B, H, S, hd)``, around the call,
  so each block's matmuls batch over the leading KV-head dim.  A
  token-major ``(bq, H, hd)`` block made Mosaic unroll the matmuls per
  (row, head): minutes of compile at 36 heads, against seconds now.
* ``page_idx``/``cache_len``/``new_lens`` ride in as **scalar-prefetch**
  operands (``PrefetchScalarGridSpec``): the index map reads
  ``page_idx[b, p]`` to pick which page tile the next grid step DMAs — the
  gather happens in the block-fetch pipeline, never as a materialized
  ``take``.
* the walk is bounded per (row, q-block): the block needs the lanes up to
  the smaller of the row's last page, ``ceil(cache_len / page_size)``, and
  its causal end, ``q_pos_max // page_size + 1`` with ``q_pos_max`` the
  position of its last column; a block of padding columns only (every
  block of a ``new_lens == 0`` row) needs none.  Every lane past that
  names the block of the last needed lane (lane 0 where none is needed),
  so consecutive dead steps name one block and the pipeline issues no
  DMA for them, and the kernel body skips their einsums, exps and
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

from .paged_attn import live_lanes


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
    """Kernel factory.  ``quantized``: the page blocks are int8 and each is
    followed by its (1, KVH, 1) float32 per-page scale block (fetched
    through the SAME page-index map); dequantization is one cast +
    broadcast multiply at DMA time, inside VMEM — no fp32 copy of any page
    ever exists outside the kernel.

    Queries arrive head-major, ``(H, bq, hd)`` per block, so every matmul
    is one batched dot over the KV heads with the batch dim leading: the
    query-block rows of a KV head's ``g`` grouped heads stack into one
    ``(g * bq, hd)`` operand."""

    def kernel(pi_ref, cl_ref, nl_ref, q_ref, *refs):
        if quantized:
            k_ref, v_ref, ks_ref, vs_ref = refs[:4]
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

        ps, kvh, hd = k_ref.shape[1], k_ref.shape[2], k_ref.shape[3]
        h, bq = q_ref.shape[1], q_ref.shape[2]
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
            valid = valid[None]                            # (1, g*bq, ps)

            # (H, bq, hd) -> (KVH, g * bq, hd): heads grouped by kv head
            qh = q_ref[0].astype(jnp.float32).reshape(kvh, g * bq, hd)
            if quantized:
                k = k_ref[0].astype(jnp.float32) * ks_ref[0][None]
                v = v_ref[0].astype(jnp.float32) * vs_ref[0][None]
            else:
                k = k_ref[0].astype(jnp.float32)           # (ps, KVH, hd)
                v = v_ref[0].astype(jnp.float32)
            s = jnp.einsum("kqd,skd->kqs", qh, k,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, -jnp.inf)              # (KVH, g*bq, ps)

            m_prev = m_ref[...]                            # (KVH, g*bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pexp = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
            corr = jnp.where(jnp.isfinite(m_prev),
                             jnp.exp(m_prev - m_safe), 0.0)
            l_ref[...] = l_ref[...] * corr \
                + jnp.sum(pexp, axis=2, keepdims=True)
            pv = jnp.einsum("kqs,skd->kqd", pexp, v,
                            preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * corr + pv
            m_ref[...] = m_new

        @pl.when(p == n_p - 1)
        def _emit():
            l = jnp.maximum(l_ref[...], 1e-20)             # fully-masked rows
            o_ref[0] = (acc_ref[...] / l).reshape(h, bq, hd).astype(
                o_ref.dtype)                               # (padding) emit 0
    return kernel


def _chunk_attn_common(q, kv_operands, page_idx, cache_len, new_lens,
                       interpret, block_q):
    """Shared call-path for the fp32 and quantized kernels.
    ``kv_operands`` is (k_pages, v_pages[, k_scale, v_scale])."""
    b, s, h, hd = q.shape
    _, ps, kvh, _ = kv_operands[0].shape
    n_p = page_idx.shape[1]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    bq = block_q or _pick_block_q(s)
    assert s % bq == 0, (s, bq)
    n_q = s // bq
    quantized = len(kv_operands) == 4
    if quantized:     # (n_pages, KVH) -> (n_pages, KVH, 1): a (1, KVH, 1)
        #               block spans the array's two minor dims, as the TPU
        #               tiling rule requires
        kv_operands = kv_operands[:2] + tuple(
            x[:, :, None] for x in kv_operands[2:])

    def page(bi, qi, p, idx_ref, cl_ref, nl_ref):
        # a dead lane names the last needed lane's page: no new DMA
        last = jnp.maximum(
            _needed_lanes(cl_ref[bi], nl_ref[bi], qi, s, bq, ps) - 1, 0)
        return jnp.maximum(idx_ref[bi, jnp.minimum(p, last)], 0)

    def kv_map(*a):
        return (page(*a), 0, 0, 0)

    def scale_map(*a):
        return (page(*a), 0, 0)

    def q_map(bi, qi, p, idx_ref, cl_ref, nl_ref):
        return (bi, 0, qi, 0)

    in_specs = [pl.BlockSpec((1, h, bq, hd), q_map),
                pl.BlockSpec((1, ps, kvh, hd), kv_map),
                pl.BlockSpec((1, ps, kvh, hd), kv_map)]
    if quantized:
        in_specs += [pl.BlockSpec((1, kvh, 1), scale_map),
                     pl.BlockSpec((1, kvh, 1), scale_map)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # page_idx, cache_len, new_lens
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
      new_lens.astype(jnp.int32), q.transpose(0, 2, 1, 3), *kv_operands)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("interpret", "block_q"))
def _chunk_attn_call(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     page_idx: jax.Array, cache_len: jax.Array,
                     new_lens: jax.Array, interpret: bool = False,
                     block_q: int = 0) -> jax.Array:
    """q: (B, S, H, hd) right-aligned chunks; k/v_pages: (n_pages, ps, KVH,
    hd); page_idx: (B, P) int32 (-1 = unused lane); cache_len: (B,) total
    valid length AFTER the chunk; new_lens: (B,) valid trailing columns.
    -> (B, S, H, hd) (padding columns zero)."""
    return _chunk_attn_common(q, (k_pages, v_pages), page_idx, cache_len,
                              new_lens, interpret, block_q)


@functools.partial(jax.jit, static_argnames=("interpret", "block_q"))
def _chunk_attn_quant_call(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, k_scale: jax.Array,
                           v_scale: jax.Array, page_idx: jax.Array,
                           cache_len: jax.Array, new_lens: jax.Array,
                           interpret: bool = False,
                           block_q: int = 0) -> jax.Array:
    """Quantized-pool variant: k/v_pages are (n_pages, ps, KVH, hd) int8
    and k/v_scale (n_pages, KVH) float32 per-page scales; both ride the
    same scalar-prefetched page-index path and pages dequantize in VMEM
    (``kernels.quant``).  Same shapes/masking otherwise."""
    return _chunk_attn_common(q, (k_pages, v_pages, k_scale, v_scale),
                              page_idx, cache_len, new_lens, interpret,
                              block_q)
