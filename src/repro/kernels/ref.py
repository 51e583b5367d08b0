"""Pure-jnp oracles for the table and paged-attention kernels (used by the
allclose test sweeps and as the CPU fallback path)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .paged_attn import NT, gather_out, head_scales, spread_q


def scan_ref(table2d: jax.Array, lock_id) -> tuple[jax.Array, jax.Array]:
    """-> (mask int8 (rows,128), count int32 scalar)."""
    m = table2d == jnp.asarray(lock_id, table2d.dtype)
    return m.astype(jnp.int8), jnp.sum(m.astype(jnp.int32))


def publish_ref(table2d: jax.Array, slots: jax.Array, ids: jax.Array,
                unconditional: bool = False):
    """Sequential-CAS semantics: the first request for a free slot wins.

    -> (new table, granted bool (M,)).
    """
    rows, lanes = table2d.shape
    flat = table2d.reshape(-1)
    m = slots.shape[0]
    idx = jnp.arange(m)
    dup_earlier = (slots[None, :] == slots[:, None]) & (idx[None, :]
                                                        < idx[:, None])
    first = ~jnp.any(dup_earlier, axis=1)
    if unconditional:
        granted = jnp.ones((m,), jnp.bool_)
        # duplicate slots: callers use unique slots or identical ids (clear)
        new_flat = flat.at[slots].set(ids.astype(flat.dtype))
    else:
        free = flat[slots] == 0
        granted = first & free
        # scatter only the granted requests (losers drop out of bounds)
        new_flat = flat.at[jnp.where(granted, slots, flat.size)].set(
            ids.astype(flat.dtype), mode="drop")
    return new_flat.reshape(rows, lanes), granted


def clear_ref(table2d: jax.Array, slots: jax.Array):
    zeros = jnp.zeros_like(slots)
    return publish_ref(table2d, slots, zeros, unconditional=True)[0]


def publish_multi_ref(table2d: jax.Array, rbias_vec: jax.Array,
                      slots: jax.Array, lock_idx: jax.Array,
                      ids: jax.Array):
    """Sequential-CAS semantics with per-request lock bias: a request whose
    lock's bias is clear never attempts its CAS (so it neither wins nor
    shadows a later in-batch request for the same slot).

    -> (new table, granted bool (M,)).
    """
    rows, lanes = table2d.shape
    flat = table2d.reshape(-1)
    m = slots.shape[0]
    idx = jnp.arange(m)
    biased = rbias_vec[lock_idx] != 0
    dup_earlier = (slots[None, :] == slots[:, None]) \
        & (idx[None, :] < idx[:, None]) & biased[None, :]
    first = ~jnp.any(dup_earlier, axis=1)
    free = flat[slots] == 0
    granted = first & free & biased
    new_flat = flat.at[jnp.where(granted, slots, flat.size)].set(
        ids.astype(flat.dtype), mode="drop")
    return new_flat.reshape(rows, lanes), granted


def multi_count_ref(table2d: jax.Array, lock_ids: jax.Array) -> jax.Array:
    """-> (K,) int32 exact hold counts (oracle for revocation_poll_multi)."""
    return jnp.sum((table2d.reshape(-1)[:, None]
                    == lock_ids[None, :].astype(table2d.dtype))
                   .astype(jnp.int32), axis=0)


def one_layer(x: jax.Array) -> jax.Array:
    """Pages ``(n_pages, ps, KVH, hd)`` -> a one-layer store leaf ``(1,
    n_pages, ps, KVH * hd)`` in the store's lane-dense layout; per-page
    scales ``(n_pages, KVH)`` -> ``(1, n_pages, KVH)``."""
    if x.ndim == 4:
        n, ps, kvh, hd = x.shape
        return x.reshape(1, n, ps, kvh * hd)
    return x[None]


def _decode_walk(q, store, layer, page_idx, cache_len):
    """The decode kernel's page walk, op for op, as the kernel's grid runs
    it (a loop over rows, in it a loop over pages): ``store`` is (k_pages,
    v_pages) or, for the int8 pool, (k_pages, v_pages, k_scale,
    v_scale)."""
    b, h, hd = q.shape
    _, _, ps, lanes = store[0].shape
    kvh = lanes // hd
    n_p = page_idx.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quantized = len(store) == 4

    def row(bi):
        qbd = spread_q(q[bi].astype(jnp.float32), kvh)

        def step(p, carry):
            m, den, acc = carry
            page = page_idx[bi, p]
            i = jnp.maximum(page, 0)
            k = store[0][layer, i].astype(jnp.float32)     # (ps, KVH * hd)
            v = store[1][layer, i].astype(jnp.float32)
            pos = p * ps + jnp.arange(ps)[None, :]
            valid = (pos < cache_len[bi]) & (page >= 0)        # (1, ps)
            s = jax.lax.dot_general(qbd, k, NT,
                                    preferred_element_type=jnp.float32)
            if quantized:
                s = s * head_scales(store[2][layer, i][None, :], h)
            s = jnp.where(valid, s * scale, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pexp = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            den = den * corr + jnp.sum(pexp, axis=1, keepdims=True)
            pv = jnp.dot(pexp, v, preferred_element_type=jnp.float32)
            if quantized:
                pv = pv * head_scales(store[3][layer, i][None, :], h)
            return m_new, den, acc * corr + pv

        m, den, acc = jax.lax.fori_loop(
            0, n_p, step, (jnp.full((h, 1), -jnp.inf, jnp.float32),
                           jnp.zeros((h, 1), jnp.float32),
                           jnp.zeros((h, lanes), jnp.float32)))
        return gather_out(acc / jnp.maximum(den, 1e-20), hd)

    return jax.lax.map(row, jnp.arange(b)).astype(q.dtype)


def paged_attn_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                   layer, page_idx: jax.Array,
                   cache_len: jax.Array) -> jax.Array:
    """Oracle for the gather-by-page decode attention kernel.

    Walks the page-index vector in the SAME order as the kernel's grid
    (online softmax, one page per step, identical per-request matmuls) so
    interpret-mode runs can be compared bit for bit, not just allclose —
    run the oracle under ``jax.jit`` for the comparison, so both sides get
    the same XLA fusion (FMA contraction) of the accumulator update.
    q: (B, H, hd); k/v_pages: (L, n_pages, ps, KVH * hd); layer: int32
    scalar; page_idx: (B, P) int32 (-1 = unused); cache_len: (B,).
    -> (B, H, hd).
    """
    return _decode_walk(q, (k_pages, v_pages), layer, page_idx, cache_len)


def paged_attn_quant_ref(q: jax.Array, k_pages: jax.Array,
                         v_pages: jax.Array, k_scale: jax.Array,
                         v_scale: jax.Array, layer, page_idx: jax.Array,
                         cache_len: jax.Array) -> jax.Array:
    """Oracle for the quantized decode kernel: the page walk of
    :func:`paged_attn_ref` with the kernel's exact scale op order (the
    int8 matmuls, then each query head's KV-head scale on the scores and
    on ``p @ v``), so interpret-mode runs compare bit for bit.  k/v_pages
    int8, k/v_scale (L, n_pages, KVH) float32."""
    return _decode_walk(q, (k_pages, v_pages, k_scale, v_scale), layer,
                        page_idx, cache_len)


def _chunk_walk(q, store, layer, page_idx, cache_len, new_lens, block_q):
    """The chunk kernel's walk, op for op, as its grid runs it (loops over
    rows, q-blocks and pages; in each page, one KV head after the other):
    ``store`` is (k_pages, v_pages) or (k_pages, v_pages, k_scale,
    v_scale)."""
    from .paged_chunk_attn import _pick_block_q

    b, s, h, hd = q.shape
    _, _, ps, lanes = store[0].shape
    kvh = lanes // hd
    n_p = page_idx.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    quantized = len(store) == 4
    bq = block_q or _pick_block_q(s)
    assert s % bq == 0, (s, bq)      # same contract as the kernel call

    def block(bi, qi):
        col = qi * bq + jnp.arange(bq)[:, None]                # (bq, 1)
        q_pos = cache_len[bi] - s + col
        valid_q = (col >= s - new_lens[bi]) & (q_pos >= 0)
        # the head-major block: (H, bq, hd)
        qb = jax.lax.dynamic_slice_in_dim(q[bi], qi * bq, bq).transpose(
            1, 0, 2)

        def step(p, carry):
            m, den, acc = carry
            page = page_idx[bi, p]
            i = jnp.maximum(page, 0)
            t_pos = p * ps + jnp.arange(ps)[None, :]           # (1, ps)
            valid = (t_pos < cache_len[bi]) & (page >= 0) \
                & (t_pos <= q_pos) & valid_q
            valid = jnp.concatenate([valid] * g, axis=0)
            for c in range(kvh):
                sl = slice(c * hd, (c + 1) * hd)
                qh = qb[c * g:(c + 1) * g].astype(jnp.float32).reshape(
                    g * bq, hd)
                k = store[0][layer, i][:, sl].astype(jnp.float32)
                v = store[1][layer, i][:, sl].astype(jnp.float32)
                if quantized:
                    k = k * store[2][layer, i, c]
                    v = v * store[3][layer, i, c]
                sc = jax.lax.dot_general(
                    qh, k, NT, preferred_element_type=jnp.float32) * scale
                sc = jnp.where(valid, sc, -jnp.inf)
                m_new = jnp.maximum(m[c], jnp.max(sc, axis=1, keepdims=True))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                pexp = jnp.where(valid, jnp.exp(sc - m_safe), 0.0)
                corr = jnp.where(jnp.isfinite(m[c]),
                                 jnp.exp(m[c] - m_safe), 0.0)
                den = den.at[c].set(den[c] * corr
                                    + jnp.sum(pexp, axis=1, keepdims=True))
                acc = acc.at[c].set(acc[c] * corr + jnp.dot(
                    pexp, v, preferred_element_type=jnp.float32))
                m = m.at[c].set(m_new)
            return m, den, acc

        m, den, acc = jax.lax.fori_loop(
            0, n_p, step,
            (jnp.full((kvh, g * bq, 1), -jnp.inf, jnp.float32),
             jnp.zeros((kvh, g * bq, 1), jnp.float32),
             jnp.zeros((kvh, g * bq, hd), jnp.float32)))
        out = acc / jnp.maximum(den, 1e-20)
        return out.reshape(h, bq, hd).transpose(1, 0, 2)       # (bq, H, hd)

    out = jax.lax.map(lambda bi: jax.lax.map(
        lambda qi: block(bi, qi), jnp.arange(s // bq)), jnp.arange(b))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def paged_chunk_attn_ref(q: jax.Array, k_pages: jax.Array,
                         v_pages: jax.Array, layer, page_idx: jax.Array,
                         cache_len: jax.Array, new_lens: jax.Array,
                         block_q: int = 0) -> jax.Array:
    """Oracle for the streaming chunk-prefill attention kernel.

    Walks (row, q-block, page, KV head) in the SAME order as the kernel
    (online softmax, one page per inner step, identical per-KV-head
    matmuls) so interpret-mode runs can be compared bit for bit — run the
    oracle under ``jax.jit`` for the comparison, like
    :func:`paged_attn_ref`.  q: (B, S, H, hd) right-aligned chunks;
    k/v_pages: (L, n_pages, ps, KVH * hd); layer: int32 scalar; page_idx:
    (B, P) int32 (-1 = unused); cache_len: (B,) total valid length AFTER
    the chunk; new_lens: (B,) valid trailing columns.  -> (B, S, H, hd)
    (padding columns zero).
    """
    return _chunk_walk(q, (k_pages, v_pages), layer, page_idx, cache_len,
                       new_lens, block_q)


def paged_chunk_attn_quant_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, k_scale: jax.Array,
                               v_scale: jax.Array, layer,
                               page_idx: jax.Array, cache_len: jax.Array,
                               new_lens: jax.Array,
                               block_q: int = 0) -> jax.Array:
    """Oracle for the quantized chunk-prefill kernel: the walk of
    :func:`paged_chunk_attn_ref` with the kernel's exact dequant op order
    (a KV head's int8 lanes cast, then times its page scale)."""
    return _chunk_walk(q, (k_pages, v_pages, k_scale, v_scale), layer,
                       page_idx, cache_len, new_lens, block_q)


def paged_chunk_dense_ref(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, layer, page_idx: jax.Array,
                          cache_len: jax.Array,
                          new_lens: jax.Array) -> jax.Array:
    """The PR-4 dense chunk-attention path (gather every page of ``layer``
    into a contiguous ``(B, lanes * ps, KVH, hd)`` buffer, one full
    softmax): kept as the allclose cross-check and the benchmark's dense
    baseline — this materialization is exactly what the streaming kernel
    avoids."""
    b, s, h, hd = q.shape
    _, _, ps, lanes = k_pages.shape
    kvh = lanes // hd
    n_lanes = page_idx.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    q_pos = cache_len[:, None] - s + jnp.arange(s)[None, :]       # (B, S)
    valid_q = (jnp.arange(s)[None, :] >= s - new_lens[:, None]) \
        & (q_pos >= 0)
    safe = jnp.clip(page_idx, 0)
    kd = k_pages[layer][safe].reshape(b, n_lanes * ps, kvh, hd).astype(
        jnp.float32)
    vd = v_pages[layer][safe].reshape(b, n_lanes * ps, kvh, hd).astype(
        jnp.float32)
    t = jnp.arange(n_lanes * ps)
    valid_t = (t[None, :] < cache_len[:, None]) \
        & jnp.repeat(page_idx >= 0, ps, axis=1)                   # (B, T)
    qh = q.astype(jnp.float32).reshape(b, s, kvh, g, hd)
    sc = jnp.einsum("bskgd,btkd->bkgst", qh, kd,
                    preferred_element_type=jnp.float32) * scale
    mask = valid_t[:, None, None, None, :] \
        & (t[None, None, None, None, :] <= q_pos[:, None, None, :, None]) \
        & valid_q[:, None, None, :, None]
    sc = jnp.where(mask, sc, -jnp.inf)
    m = jnp.max(sc, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)     # fully-masked (padded) rows
    pexp = jnp.where(mask, jnp.exp(sc - m), 0.0)
    den = jnp.maximum(jnp.sum(pexp, axis=-1, keepdims=True), 1e-20)
    o = jnp.einsum("bkgst,btkd->bskgd", pexp / den, vd,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, s, h, hd).astype(q.dtype)
