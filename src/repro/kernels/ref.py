"""Pure-jnp oracles for the table and paged-attention kernels (used by the
allclose test sweeps and as the CPU fallback path)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


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


def paged_attn_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                   page_idx: jax.Array, cache_len: jax.Array) -> jax.Array:
    """Oracle for the gather-by-page decode attention kernel.

    Walks the page-index vector in the SAME order as the kernel's grid
    (online softmax, one page per step, identical per-request einsums) so
    interpret-mode runs can be compared bit for bit, not just allclose —
    run the oracle under ``jax.jit`` for the comparison, so both sides get
    the same XLA fusion (FMA contraction) of the accumulator update.
    q: (B, H, hd); k/v_pages: (n_pages, ps, KVH, hd); page_idx: (B, P)
    int32 (-1 = unused); cache_len: (B,).  -> (B, H, hd).
    """
    b, h, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    n_p = page_idx.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    outs = []
    for bi in range(b):       # per request, exactly one grid row's ops
        qh = q[bi].astype(jnp.float32).reshape(kvh, g, hd)
        m = jnp.full((h, 1), -jnp.inf, jnp.float32)
        den = jnp.zeros((h, 1), jnp.float32)
        acc = jnp.zeros((h, hd), jnp.float32)
        for p in range(n_p):
            page = page_idx[bi, p]
            k = k_pages[jnp.clip(page, 0)].astype(jnp.float32)
            v = v_pages[jnp.clip(page, 0)].astype(jnp.float32)
            pos = p * ps + jnp.arange(ps)[None, :]
            valid = (pos < cache_len[bi]) & (page >= 0)        # (1, ps)
            s = jnp.einsum("kgd,skd->kgs", qh, k,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s.reshape(h, ps), -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pexp = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            den = den * corr + jnp.sum(pexp, axis=1, keepdims=True)
            pv = jnp.einsum("kgs,skd->kgd", pexp.reshape(kvh, g, ps), v,
                            preferred_element_type=jnp.float32)
            acc = acc * corr + pv.reshape(h, hd)
            m = m_new
        outs.append(acc / jnp.maximum(den, 1e-20))
    return jnp.stack(outs).astype(q.dtype)


def paged_chunk_attn_ref(q: jax.Array, k_pages: jax.Array,
                         v_pages: jax.Array, page_idx: jax.Array,
                         cache_len: jax.Array, new_lens: jax.Array,
                         block_q: int = 0) -> jax.Array:
    """Oracle for the streaming chunk-prefill attention kernel.

    Walks (row, q-block, page) in the SAME order as the kernel's grid
    (online softmax, one page per inner step, identical per-block einsums)
    so interpret-mode runs can be compared bit for bit — run the oracle
    under ``jax.jit`` for the comparison, like :func:`paged_attn_ref`.
    q: (B, S, H, hd) right-aligned chunks; k/v_pages: (n_pages, ps, KVH,
    hd); page_idx: (B, P) int32 (-1 = unused); cache_len: (B,) total valid
    length AFTER the chunk; new_lens: (B,) valid trailing columns.
    -> (B, S, H, hd) (padding columns zero).
    """
    from .paged_chunk_attn import _pick_block_q

    b, s, h, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    n_p = page_idx.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    bq = block_q or _pick_block_q(s)
    assert s % bq == 0, (s, bq)      # same contract as the kernel call
    outs = []
    for bi in range(b):
        rows = []
        for qi in range(s // bq):
            col = qi * bq + jnp.arange(bq)[:, None]            # (bq, 1)
            q_pos = cache_len[bi] - s + col
            valid_q = (col >= s - new_lens[bi]) & (q_pos >= 0)
            # head-major block, heads grouped by kv head: (KVH, g*bq, hd)
            qh = q[bi, qi * bq:(qi + 1) * bq].astype(jnp.float32) \
                .transpose(1, 0, 2).reshape(kvh, g * bq, hd)
            m = jnp.full((kvh, g * bq, 1), -jnp.inf, jnp.float32)
            den = jnp.zeros((kvh, g * bq, 1), jnp.float32)
            acc = jnp.zeros((kvh, g * bq, hd), jnp.float32)
            for p in range(n_p):
                page = page_idx[bi, p]
                k = k_pages[jnp.clip(page, 0)].astype(jnp.float32)
                v = v_pages[jnp.clip(page, 0)].astype(jnp.float32)
                t_pos = p * ps + jnp.arange(ps)[None, :]       # (1, ps)
                valid = (t_pos < cache_len[bi]) & (page >= 0) \
                    & (t_pos <= q_pos) & valid_q
                valid = jnp.concatenate([valid] * g, axis=0)[None]
                sc = jnp.einsum("kqd,skd->kqs", qh, k,
                                preferred_element_type=jnp.float32) * scale
                sc = jnp.where(valid, sc, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(sc, axis=2, keepdims=True))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                pexp = jnp.where(valid, jnp.exp(sc - m_safe), 0.0)
                corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
                den = den * corr + jnp.sum(pexp, axis=2, keepdims=True)
                pv = jnp.einsum("kqs,skd->kqd", pexp, v,
                                preferred_element_type=jnp.float32)
                acc = acc * corr + pv
                m = m_new
            out = acc / jnp.maximum(den, 1e-20)
            rows.append(out.reshape(h, bq, hd).transpose(1, 0, 2))
        outs.append(jnp.concatenate(rows, axis=0))
    return jnp.stack(outs).astype(q.dtype)


def paged_attn_quant_ref(q: jax.Array, k_pages: jax.Array,
                         v_pages: jax.Array, k_scale: jax.Array,
                         v_scale: jax.Array, page_idx: jax.Array,
                         cache_len: jax.Array) -> jax.Array:
    """Oracle for the quantized decode kernel: identical page walk to
    :func:`paged_attn_ref`, with the kernel's exact dequant op order
    (int8 ``astype`` then one broadcast scale multiply per page) so
    interpret-mode runs compare bit for bit.  k/v_pages int8, k/v_scale
    (n_pages, KVH) float32."""

    def deq(pages, scales, page):
        i = jnp.clip(page, 0)
        return pages[i].astype(jnp.float32) * scales[i][None, :, None]

    b, h, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    n_p = page_idx.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    outs = []
    for bi in range(b):
        qh = q[bi].astype(jnp.float32).reshape(kvh, g, hd)
        m = jnp.full((h, 1), -jnp.inf, jnp.float32)
        den = jnp.zeros((h, 1), jnp.float32)
        acc = jnp.zeros((h, hd), jnp.float32)
        for p in range(n_p):
            page = page_idx[bi, p]
            k = deq(k_pages, k_scale, page)
            v = deq(v_pages, v_scale, page)
            pos = p * ps + jnp.arange(ps)[None, :]
            valid = (pos < cache_len[bi]) & (page >= 0)
            s = jnp.einsum("kgd,skd->kgs", qh, k,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s.reshape(h, ps), -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pexp = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            den = den * corr + jnp.sum(pexp, axis=1, keepdims=True)
            pv = jnp.einsum("kgs,skd->kgd", pexp.reshape(kvh, g, ps), v,
                            preferred_element_type=jnp.float32)
            acc = acc * corr + pv.reshape(h, hd)
            m = m_new
        outs.append(acc / jnp.maximum(den, 1e-20))
    return jnp.stack(outs).astype(q.dtype)


def paged_chunk_attn_quant_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, k_scale: jax.Array,
                               v_scale: jax.Array, page_idx: jax.Array,
                               cache_len: jax.Array, new_lens: jax.Array,
                               block_q: int = 0) -> jax.Array:
    """Oracle for the quantized chunk-prefill kernel: identical (row,
    q-block, page) walk to :func:`paged_chunk_attn_ref` with the kernel's
    exact dequant op order."""
    from .paged_chunk_attn import _pick_block_q

    def deq(pages, scales, page):
        i = jnp.clip(page, 0)
        return pages[i].astype(jnp.float32) * scales[i][None, :, None]

    b, s, h, hd = q.shape
    _, ps, kvh, _ = k_pages.shape
    n_p = page_idx.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    bq = block_q or _pick_block_q(s)
    assert s % bq == 0, (s, bq)
    outs = []
    for bi in range(b):
        rows = []
        for qi in range(s // bq):
            col = qi * bq + jnp.arange(bq)[:, None]            # (bq, 1)
            q_pos = cache_len[bi] - s + col
            valid_q = (col >= s - new_lens[bi]) & (q_pos >= 0)
            # head-major block, heads grouped by kv head: (KVH, g*bq, hd)
            qh = q[bi, qi * bq:(qi + 1) * bq].astype(jnp.float32) \
                .transpose(1, 0, 2).reshape(kvh, g * bq, hd)
            m = jnp.full((kvh, g * bq, 1), -jnp.inf, jnp.float32)
            den = jnp.zeros((kvh, g * bq, 1), jnp.float32)
            acc = jnp.zeros((kvh, g * bq, hd), jnp.float32)
            for p in range(n_p):
                page = page_idx[bi, p]
                k = deq(k_pages, k_scale, page)
                v = deq(v_pages, v_scale, page)
                t_pos = p * ps + jnp.arange(ps)[None, :]       # (1, ps)
                valid = (t_pos < cache_len[bi]) & (page >= 0) \
                    & (t_pos <= q_pos) & valid_q
                valid = jnp.concatenate([valid] * g, axis=0)[None]
                sc = jnp.einsum("kqd,skd->kqs", qh, k,
                                preferred_element_type=jnp.float32) * scale
                sc = jnp.where(valid, sc, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(sc, axis=2, keepdims=True))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                pexp = jnp.where(valid, jnp.exp(sc - m_safe), 0.0)
                corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
                den = den * corr + jnp.sum(pexp, axis=2, keepdims=True)
                pv = jnp.einsum("kqs,skd->kqd", pexp, v,
                                preferred_element_type=jnp.float32)
                acc = acc * corr + pv
                m = m_new
            out = acc / jnp.maximum(den, 1e-20)
            rows.append(out.reshape(h, bq, hd).transpose(1, 0, 2))
        outs.append(jnp.concatenate(rows, axis=0))
    return jnp.stack(outs).astype(q.dtype)


def paged_chunk_dense_ref(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, page_idx: jax.Array,
                          cache_len: jax.Array,
                          new_lens: jax.Array) -> jax.Array:
    """The PR-4 dense chunk-attention path (gather every page into a
    contiguous ``(B, lanes * ps, KVH, hd)`` buffer, one full softmax):
    kept as the allclose cross-check and the benchmark's dense baseline —
    this materialization is exactly what the streaming kernel avoids."""
    b, s, h, hd = q.shape
    n_pages, ps, kvh, _ = k_pages.shape
    n_lanes = page_idx.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(hd)
    q_pos = cache_len[:, None] - s + jnp.arange(s)[None, :]       # (B, S)
    valid_q = (jnp.arange(s)[None, :] >= s - new_lens[:, None]) \
        & (q_pos >= 0)
    safe = jnp.clip(page_idx, 0)
    kd = k_pages[safe].reshape(b, n_lanes * ps, kvh, hd).astype(jnp.float32)
    vd = v_pages[safe].reshape(b, n_lanes * ps, kvh, hd).astype(jnp.float32)
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
