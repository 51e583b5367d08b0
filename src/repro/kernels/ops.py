"""Public jit'd wrappers for the table kernels.

On TPU the kernels compile to Mosaic.  On the CPU backend they run in
``interpret=True`` mode (the Pallas body executes as plain JAX ops — the
validation path of the test suite).  Any other backend raises: these are
TPU kernels, and silently interpreting them elsewhere would hide the
device the program was meant to run on.
"""

from __future__ import annotations

import functools
import json
import pathlib

import jax
import jax.numpy as jnp

from .paged_attn import _paged_attn_call, _paged_attn_quant_call
from .paged_chunk_attn import _chunk_attn_call, _chunk_attn_quant_call
from .table_publish import (_fused_publish_call, _fused_publish_multi_call,
                            _publish_call)
from .table_scan import LANES, _multi_poll_call, _poll_call, _scan_call

__all__ = ["as_table2d", "revocation_scan", "revocation_poll",
           "revocation_poll_multi", "publish", "clear", "fused_publish",
           "fused_publish_multi", "fused_clear", "paged_attention",
           "paged_attention_quant", "paged_chunk_attention",
           "paged_chunk_attention_quant", "jit_donating", "LANES"]


def _interpret() -> bool:
    """False on TPU (Mosaic), True on the CPU validation backend; an error
    on any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas TPU kernels run compiled on a TPU or interpreted on "
        f"the CPU backend; the default backend is {backend!r}")


# --------------------------------------------------------------------------
# Autotune table: ``kernels/autotune.py`` sweeps the paged kernels' knobs
# (pages-per-DMA-lane for decode, q-block height for chunk prefill) per
# backend and persists the winners next to this module; the wrappers below
# read them here.  Missing file / backend / knob falls back to the default
# — an untuned backend is never an error.
# --------------------------------------------------------------------------

_TUNING_PATH = pathlib.Path(__file__).with_name("tuning_table.json")


@functools.lru_cache(maxsize=None)
def _tuning() -> dict:
    try:
        return json.loads(_TUNING_PATH.read_text())
    except (OSError, ValueError):
        return {}


@functools.lru_cache(maxsize=None)
def _tuned(kernel: str, knob: str, default: int) -> int:
    entry = _tuning().get(kernel, {}).get(jax.default_backend(), {})
    v = entry.get(knob, default)
    return v if isinstance(v, int) and v > 0 else default


def jit_donating(fn, n_donated: int, **jit_kw):
    """``jax.jit`` donating the first ``n_donated`` args — except on CPU
    (the validation backend), which ignores donation and would warn on
    every compile.  One policy for every lease/registry/pool program."""
    donating = jax.default_backend() != "cpu"
    return jax.jit(fn, donate_argnums=tuple(range(n_donated))
                   if donating else (), **jit_kw)


def as_table2d(table_flat: jax.Array) -> jax.Array:
    n = table_flat.shape[0]
    assert n % LANES == 0, n
    return table_flat.reshape(n // LANES, LANES)


def revocation_scan(table2d: jax.Array, lock_id) -> tuple[jax.Array,
                                                          jax.Array]:
    """VPU scan for a revoking writer: -> (match mask int8, match count)."""
    return _scan_call(table2d, jnp.asarray(lock_id, table2d.dtype),
                      interpret=_interpret())


def publish(table2d: jax.Array, slots: jax.Array, ids: jax.Array):
    """Batched CAS(0 -> id): -> (new table, granted bool (M,))."""
    return _publish_call(table2d, slots, ids, interpret=_interpret(),
                         unconditional=False)


def clear(table2d: jax.Array, slots: jax.Array) -> jax.Array:
    """Release: store 0 into each slot."""
    zeros = jnp.zeros_like(slots)
    out, _ = _publish_call(table2d, slots, zeros, interpret=_interpret(),
                           unconditional=True)
    return out


# --------------------------------------------------------------------------
# Fused/aliased fast path (device-BRAVO): the table buffer is donated into
# the kernel (``input_output_aliases``) — no per-call 16KB copy — and the
# rbias recheck + conditional undo happen in kernel, so callers never sync.
# --------------------------------------------------------------------------


def fused_publish(table2d: jax.Array, rbias: jax.Array, slots: jax.Array,
                  ids: jax.Array):
    """Vectorized batched CAS(0 -> id), masked by ``rbias != 0`` in kernel.

    -> (new table [in place], granted bool (M,)).  The input table buffer is
    consumed (aliased); callers must use the returned array."""
    return _fused_publish_call(table2d, rbias, slots, ids,
                               interpret=_interpret(), unconditional=False,
                               check_rbias=True)


def fused_clear(table2d: jax.Array, slots: jax.Array) -> jax.Array:
    """Release: store 0 into each slot, in place (aliased, unconditional)."""
    zeros = jnp.zeros_like(slots, jnp.int32)
    out, _ = _fused_publish_call(table2d, jnp.ones((), jnp.int32), slots,
                                 zeros, interpret=_interpret(),
                                 unconditional=True, check_rbias=False)
    return out


def fused_publish_multi(table2d: jax.Array, rbias_vec: jax.Array,
                        slots: jax.Array, lock_idx: jax.Array,
                        ids: jax.Array):
    """Multi-lock batched CAS(0 -> id): each request is rechecked against
    its OWN lock's bias, gathered from the registry's per-lock ``rbias_vec``
    inside the kernel (no host rbias read, no cross-lock undo).

    -> (new table [in place], granted bool (M,)).  The input table buffer is
    consumed (aliased); callers must use the returned array."""
    return _fused_publish_multi_call(table2d, rbias_vec, slots, lock_idx,
                                     ids, interpret=_interpret())


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_idx: jax.Array, cache_len: jax.Array) -> jax.Array:
    """Gather-by-page decode attention over the KV pool's page store.

    q: (B, H, hd); k/v_pages: (n_pages, page_size, KVH, hd); page_idx:
    (B, P) int32 page-index vectors (-1 = unused lane); cache_len: (B,)
    valid lengths.  -> (B, H, hd).  Each request's pages stream through
    VMEM via scalar-prefetched block indices — the dense (B, S, KVH, hd)
    cache is never materialized."""
    return _paged_attn_call(q, k_pages, v_pages, page_idx, cache_len,
                            interpret=_interpret(),
                            lanes_per_step=_tuned("paged_attn",
                                                  "lanes_per_step", 1))


def paged_attention_quant(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, k_scale: jax.Array,
                          v_scale: jax.Array, page_idx: jax.Array,
                          cache_len: jax.Array) -> jax.Array:
    """Quantized-pool decode attention: same contract as
    :func:`paged_attention` with int8 k/v_pages and (n_pages, KVH) float32
    per-page scales (``kernels.quant`` layout); pages dequantize inside
    the kernel at DMA time — no fp32 page copy is ever materialized."""
    return _paged_attn_quant_call(
        q, k_pages, v_pages, k_scale, v_scale, page_idx, cache_len,
        interpret=_interpret(),
        lanes_per_step=_tuned("paged_attn_quant", "lanes_per_step", 1))


def paged_chunk_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, page_idx: jax.Array,
                          cache_len: jax.Array,
                          new_lens: jax.Array) -> jax.Array:
    """Streaming chunk-prefill attention over the KV pool's page store.

    q: (B, S, H, hd) right-aligned prompt chunks; k/v_pages: (n_pages,
    page_size, KVH, hd); page_idx: (B, P) int32 (-1 = unused lane);
    cache_len: (B,) total valid length AFTER the chunk; new_lens: (B,)
    valid trailing columns per row.  -> (B, S, H, hd), padding columns
    zero.  Pages stream through VMEM via scalar-prefetched block indices —
    the dense (B, lanes * page_size, KVH, hd) gather of the PR-4 prefill
    path is never materialized."""
    s = q.shape[1]
    bq = _tuned("paged_chunk_attn", "block_q", 0)
    return _chunk_attn_call(q, k_pages, v_pages, page_idx, cache_len,
                            new_lens, interpret=_interpret(),
                            block_q=bq if bq and s % bq == 0 else 0)


def paged_chunk_attention_quant(q: jax.Array, k_pages: jax.Array,
                                v_pages: jax.Array, k_scale: jax.Array,
                                v_scale: jax.Array, page_idx: jax.Array,
                                cache_len: jax.Array,
                                new_lens: jax.Array) -> jax.Array:
    """Quantized-pool chunk-prefill attention: same contract as
    :func:`paged_chunk_attention` with int8 k/v_pages and (n_pages, KVH)
    float32 per-page scales; dequantization happens in VMEM."""
    s = q.shape[1]
    bq = _tuned("paged_chunk_attn_quant", "block_q", 0)
    return _chunk_attn_quant_call(
        q, k_pages, v_pages, k_scale, v_scale, page_idx, cache_len,
        new_lens, interpret=_interpret(),
        block_q=bq if bq and s % bq == 0 else 0)


def revocation_poll(table2d: jax.Array, lock_id) -> jax.Array:
    """Early-exit drain poll: 0 iff no slot publishes ``lock_id``; otherwise
    a positive lower bound on the hold count (see ``_poll_kernel``)."""
    return _poll_call(table2d, jnp.asarray(lock_id, table2d.dtype),
                      interpret=_interpret())


def revocation_poll_multi(table2d: jax.Array, lock_ids) -> jax.Array:
    """Exact hold counts for a vector of lock values in ONE table pass —
    the registry's many-locks drain; never touches any lock's bias."""
    return _multi_poll_call(table2d, jnp.asarray(lock_ids, table2d.dtype),
                            interpret=_interpret())
