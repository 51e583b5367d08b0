"""Jitted serving steps.

* prefill: full forward over the prompt, returning last-position logits and
  populated caches (decoder families) — also used as the encoder forward for
  encoder-only archs.
* decode (serve_step): one new token against a KV/SSM cache of length
  ``seq_len`` — this is what the ``decode_*`` / ``long_*`` dry-run shapes
  lower, per the brief.
* paged variants (the scheduler's data plane): the KV cache is the pool's
  page store (``models.model.init_paged_caches``) and every request
  addresses it through its (B, P) page-index vector from
  :class:`~repro.serving.kv_pool.KVPool` — decode reads run through the
  gather-by-page Pallas kernel (``kernels.paged_attn``), chunked prefill
  scatters right-aligned chunks into the pages.  Both are wired through
  ``dist.sharding`` (``shard_map_compat`` inside the attention layer), so
  the same step lowers on single-host and multi-host meshes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..dist.sharding import MeshRules
from ..models import model as M
from ..models.common import ModelConfig


def jit_step(fn, donate_argnums=()):
    """jit a serving step, donating the cache buffers — except on CPU (the
    validation backend), which ignores donation and would warn per compile.
    Donation keeps the page store in place across steps instead of copying
    the whole pool every token."""
    donating = jax.default_backend() != "cpu"
    return jax.jit(fn, donate_argnums=donate_argnums if donating else ())


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, rules: MeshRules):
    def prefill(params, batch):
        logits, _, caches = M.forward(params, cfg, batch, mesh=mesh,
                                      rules=rules)
        return logits[:, -1], caches
    return prefill


def make_decode_step(cfg: ModelConfig, mesh: Mesh, rules: MeshRules,
                     sample: str = "greedy", paged: bool = False):
    """decode_step(params, caches, token, cache_len[, pages]) ->
    (next_token, logits, caches').

    ``caches`` layouts come from ``models.model.init_caches``; attention
    caches hold ``cache_len - 1`` valid entries and the new K/V is written at
    ``cache_len - 1``... i.e. callers pass cache_len = old_len + 1.

    ``paged=True`` consumes the KV pool directly: ``caches`` is the page
    store from ``models.model.init_paged_caches`` and the extra ``pages``
    arg is the batch's (B, P) page-index matrix (``-1`` = unused lane;
    rows with ``cache_len == 0`` are inactive and emit token 0).  The new
    K/V land in the owning page in place and attention streams pages
    through the ``kernels.paged_attn`` kernel — no dense cache exists.
    """

    def _sample(logits):
        logits = logits[:, -1]
        if sample == "greedy":
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            raise ValueError(sample)
        return nxt[:, None], logits

    if cfg.family == "audio":
        raise ValueError("encoder-only arch has no decode step")

    if paged:
        def decode(params, caches, token, cache_len, pages):
            logits, _, caches = M.forward(params, cfg, {"tokens": token},
                                          mesh=mesh, rules=rules,
                                          caches=caches, cache_len=cache_len,
                                          pages=pages)
            nxt, logits = _sample(logits)
            return nxt, logits, caches
        return decode

    def decode(params, caches, token, cache_len):
        logits, _, caches = M.forward(params, cfg, {"tokens": token},
                                      mesh=mesh, rules=rules, caches=caches,
                                      cache_len=cache_len)
        nxt, logits = _sample(logits)
        return nxt, logits, caches

    return decode


def make_paged_prefill_step(cfg: ModelConfig, mesh: Mesh, rules: MeshRules):
    """prefill_chunk(params, caches, tokens, cache_len, chunk_lens, pages)
    -> (next_token, last_logits, caches').

    One continuous-batching prefill tick: ``tokens`` is a (R, C) batch of
    RIGHT-ALIGNED prompt chunks (row i's last ``chunk_lens[i]`` columns are
    real; leading columns are padding, masked everywhere), ``cache_len`` is
    each row's total valid length AFTER this chunk, and ``pages`` the rows'
    page-index vectors.  The chunk's K/V scatter into the page store and
    attend causally to everything already paged — so a long prompt prefills
    over several ticks without re-running earlier chunks.  Because chunks
    are right-aligned, ``next_token`` (argmax at the last column) is the
    request's first generated token whenever this was its final chunk,
    and ``last_logits`` (R, vocab) the logits it was drawn from; rows
    mid-prompt (or padding rows, ``chunk_lens == 0``) return garbage
    there, which the scheduler ignores."""

    def prefill(params, caches, tokens, cache_len, chunk_lens, pages):
        logits, _, caches = M.forward(params, cfg, {"tokens": tokens},
                                      mesh=mesh, rules=rules, caches=caches,
                                      cache_len=cache_len, pages=pages,
                                      new_lens=chunk_lens)
        last = logits[:, -1]
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return nxt, last, caches

    return prefill
