"""Int8 page quantization for the paged-KV pool (ISSUE 10 tentpole).

Decode is bandwidth-bound, so KV-page bytes are the scaling currency:
pool pages store K/V as **int8 with one float32 scale per (page, KV
head)** — symmetric absmax quantization, the act-quant pattern of the
DeepSeek-V3 fp8 exemplar (SNIPPETS.md snippet 3) applied at page
granularity so the scales ride in pool metadata exactly like BRAVO keeps
rbias/inhibit compact per lock.  Dequantization happens INSIDE the
paged-attention kernels, in VMEM (the int8 page streams in through the
scalar-prefetched page-index path; the layer's scales sit whole in
VMEM), so the lowered steps never hold a dense KV buffer or an fp32 copy
of the pool.

Page byte layout (the ROADMAP standing-constraint contract):

* content: ``(page_size, KVH * hd) int8`` per page per layer, the KV
  heads side by side on the lanes like the bf16 store — exactly half the
  bytes of the bf16 store, a quarter of fp32;
* scale: ``(KVH,) float32`` per page per layer, living in the page-store
  pytree beside the content (``{"k","v","k_scale","v_scale"}``) so the
  layer scan's carry, step donation and the engine's COW page copy treat
  content and scale as ONE unit — a COW copy that moved the bytes but not the
  scale would silently rescale the shared prefix (the
  ``cow-skips-scale`` checker mutation).

Write path: :func:`requant_scatter` merges a step's fresh K/V into the
touched pages — dequantize the touched page, scatter the new rows, zero
every slot at/after ``cache_len`` (so a freshly allocated page's scale
depends only on ITS tokens, never on stale bytes from the page's
previous owner), re-quantize, scatter back.  Only pages holding at least
one NEW token are touched, so a shared prefix page is never rewritten —
the owner-vector COW contract extends to the scales for free.

Round-trip error is bounded per element by ``scale / 2 = amax / 254``
over each (page, KV head) group; the attention-output error bound the
tests and ``benchmarks/quant.py`` gate is documented there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["QUANT_EPS", "quantize_pages", "dequantize_pages",
           "requant_scatter", "quant_layout_tag"]

# floor for the absmax so an all-zero page still gets a well-defined,
# deterministic scale (dequantizes to exact zeros either way)
QUANT_EPS = 1e-6


def quantize_pages(x: jax.Array):
    """Symmetric absmax int8 quantization over the (slot, hd) axes.

    x: ``(..., page_size, KVH, hd)`` float (the store's lanes split per KV
    head) -> ``(int8 same shape, float32 scales (..., KVH))`` with
    ``scale = max(|x|, eps) / 127`` per (page, KV head) and ``q =
    clip(round(x / scale), -127, 127)``.  The group max always maps to
    exactly ±127, so a quantize -> dequantize -> quantize round trip is
    bit-stable (same int8, same scale)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-3, -1))
    scale = jnp.maximum(amax, QUANT_EPS) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None, :, None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_pages(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_pages`: ``q (..., ps, KVH, hd) int8``
    with ``scale (..., KVH)`` -> float32.  The same op order
    (``astype`` then one broadcast multiply) as the in-kernel dequant and
    the ``ref.py`` oracles, so interpret-mode comparisons stay exact."""
    return q.astype(jnp.float32) * scale[..., None, :, None]


def requant_scatter(kq, vq, ks, vs, k_new, v_new, pages, cache_len,
                    new_lens=None, layer=0):
    """Merge a step's fresh K/V into layer ``layer`` of the quantized page
    store.

    kq/vq: ``(L, n_pages, ps, KVH * hd) int8``; ks/vs: ``(L, n_pages,
    KVH)`` float32; k_new/v_new: ``(B, S, KVH, hd)`` (right-aligned chunks
    — row i's last ``new_lens[i]`` columns are real); pages: ``(B,
    n_lanes)`` page-index vectors; cache_len: ``(B,)`` total valid length
    AFTER the chunk; layer: int32 scalar.  -> (kq', vq', ks', vs').

    The touched window per row is the static ``n_touch`` lanes starting
    at the first lane holding a NEW token (``(cache_len - new_lens) //
    ps``) — shared prefix pages sit strictly below it and are never
    gathered, rescaled or written back, which is what keeps the COW
    contract intact at the byte level.  Only ``layer``'s pages are
    gathered and scattered back; every other layer's bytes stay as they
    are.  Rows never share a touched page (pages are request-private
    while written), so the scatter-back has no conflicts by construction.
    """
    _, n_pages, ps, lanes = kq.shape
    b, s, kvh, hd = k_new.shape
    n_lanes = pages.shape[1]
    nl = (new_lens if new_lens is not None
          else jnp.full((b,), s, jnp.int32))
    n_touch = min((s + ps - 2) // ps + 1, n_lanes)

    lo = jnp.clip((cache_len - nl) // ps, 0, n_lanes - 1)          # (B,)
    lanes_t = lo[:, None] + jnp.arange(n_touch)[None, :]           # (B, T)
    lane_ok = (lanes_t < n_lanes) & (lanes_t * ps < cache_len[:, None])
    pg = jnp.take_along_axis(pages, jnp.clip(lanes_t, 0, n_lanes - 1),
                             axis=1)
    pg = jnp.where(lane_ok & (pg >= 0), pg, n_pages)       # -> drop tag
    safe = jnp.clip(pg, 0, n_pages - 1)

    def gather(x, sc):                               # (B, T, ps, KVH, hd)
        return dequantize_pages(
            x[layer, safe].reshape(b, n_touch, ps, kvh, hd), sc[layer, safe])

    kbuf = gather(kq, ks)
    vbuf = gather(vq, vs)
    # zero every slot at/after cache_len: stale bytes from the page's
    # previous life must not leak into the fresh scale
    pos = lanes_t[:, :, None] * ps + jnp.arange(ps)[None, None, :]
    keep = (pos < cache_len[:, None, None])[..., None, None]
    kbuf = jnp.where(keep, kbuf, 0.0)
    vbuf = jnp.where(keep, vbuf, 0.0)

    # scatter the new rows at their (touched-lane, offset) slots
    t_new = cache_len[:, None] - s + jnp.arange(s)[None, :]        # (B, S)
    ok = (t_new >= 0) & (jnp.arange(s)[None, :] >= s - nl[:, None])
    rel = jnp.where(ok, t_new // ps - lo[:, None], n_touch)  # OOB -> drop
    off = jnp.where(ok, t_new % ps, 0)
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s))
    kbuf = kbuf.at[bidx, rel, off].set(k_new.astype(jnp.float32),
                                       mode="drop")
    vbuf = vbuf.at[bidx, rel, off].set(v_new.astype(jnp.float32),
                                       mode="drop")

    kq2, ks2 = quantize_pages(kbuf)
    vq2, vs2 = quantize_pages(vbuf)
    dense = (b, n_touch, ps, lanes)
    return (kq.at[layer, pg].set(kq2.reshape(dense), mode="drop"),
            vq.at[layer, pg].set(vq2.reshape(dense), mode="drop"),
            ks.at[layer, pg].set(ks2, mode="drop"),
            vs.at[layer, pg].set(vs2, mode="drop"))


def quant_layout_tag(page_size: int, kvh: int, hd: int) -> int:
    """Deterministic tag for the quantized page byte layout, mixed into
    the prefix-cache key chain (``kv_pool.page_keys``) so a quantized
    page's key can never alias an entry minted for a different layout
    (fp32/bf16 pages, or a different page geometry) — dedup and COW stay
    bit-exact on the int8 bytes.  0 is reserved for the unquantized
    store (the untagged legacy chain)."""
    return (1 << 48) | (page_size << 32) | (kvh << 16) | hd
