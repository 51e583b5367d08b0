"""Lowered-step lint: structural checks over HLO/StableHLO text.

Generalizes the ad-hoc gates that grew inside ``benchmarks/prefill.py``
(dense-KV materialization), ``benchmarks/scheduler.py`` (zero transfers in
lease-held steps) and ``benchmarks/device_bravo.py`` / ``registry.py``
(donation aliasing) into one reusable checker:

* ``host-transfer-in-step`` — the *compiled* (post-optimization) HLO of a
  step that runs while KV-stripe / model-epoch leases are held must
  contain no host<->device traffic: no infeed/outfeed/send/recv, no
  cross-memory-space ``copy-start``, no python-callback custom-calls.
  Classification is :func:`repro.analysis.hlo.parse_hlo`'s transfer pass
  (trip-count aware), not a runtime counter.
* ``dense-kv-materialization`` — the lowered text of a paged step must not
  hold a dense ``(B, lanes * page_size, KVH, hd)`` gathered-KV buffer;
  the paged kernels stream pages instead of gathering them.
* ``fp32-page-materialization`` — a *quantized*-store step must keep the
  pool int8 end to end: the lowering must not hold a float32 buffer of
  the pool's lane-dense page shape (a layer's pages or the full store) —
  dequantization happens per block inside the kernel, never as a
  whole-pool upcast.
* ``missing-donation`` — buffers the engine declares donated
  (``donate_argnums``) must actually alias in the lowering.  The engine's
  ``jit_step`` disables donation on CPU (XLA:CPU ignores it), so the lint
  re-lowers each step with donation FORCED and checks the
  ``tf.aliasing_output`` / ``jax.buffer_donor`` markers — i.e. it checks
  what a TPU backend would compile.

:func:`serving_steps` builds, lowers and compiles every jitted serving
step from ``serving/engine.py`` at the smoke config; ``tests/`` applies
:func:`lint_step` to each via a fixture, and ``python -m
repro.analysis.check`` runs the same set in CI.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .hlo import parse_hlo

__all__ = [
    "Finding",
    "find_shape",
    "pool_fp32_shapes",
    "find_transfers",
    "has_donation",
    "lint_step",
    "lint_serving_steps",
    "serving_steps",
]


@dataclass
class Finding:
    rule: str
    where: str
    message: str

    def __str__(self) -> str:
        return f"{self.rule}: [{self.where}] {self.message}"


def find_shape(text: str, dims: Sequence[int],
               dtype: Optional[str] = None) -> bool:
    """True if a tensor of exactly ``dims`` appears in ``text``.  Matches
    both StableHLO (``tensor<2x64x2x16xf32>``) and HLO (``f32[2,64,2,16]``)
    spellings; anchored so ``2x64...`` does not match inside ``12x64...``
    or a longer shape.

    ``dtype=None`` matches any element type (the dense-KV rule: a gathered
    buffer is wrong at every precision).  ``dtype="f32"`` narrows the match
    to float32 tensors — the quantized-store rule, where the int8 pool
    shape is *expected* in the lowering and only its fp32 twin is a bug."""
    mlir = "x".join(str(d) for d in dims)
    hlo = ",".join(str(d) for d in dims)
    if dtype is not None:
        return bool(
            re.search(rf"(?<![0-9x]){mlir}x{dtype}\b", text)
            or re.search(rf"\b{dtype}\[{hlo}\]", text))
    return bool(
        re.search(rf"(?<![0-9x]){mlir}x[a-z]", text)
        or re.search(rf"\[{hlo}\]", text))


def has_donation(lowered_text: str) -> bool:
    """Donation aliasing markers in lowered StableHLO — present whenever
    ``donate_argnums`` reached the lowering, on any backend."""
    return ("tf.aliasing_output" in lowered_text
            or "jax.buffer_donor" in lowered_text)


def find_transfers(compiled_text: str, where: str = "") -> List[Finding]:
    """Host<->device traffic in post-optimization HLO, via the parser's
    transfer classification (trip-count multiplied)."""
    rep = parse_hlo(compiled_text)
    return [
        Finding("host-transfer-in-step", where, f"{kind} x{count}")
        for kind, count in sorted(rep.transfers.items())
    ]


def lint_step(name: str, lowered: str, compiled: Optional[str] = None,
              forbid_shapes: Iterable[Sequence[int]] = (),
              forbid_fp32_shapes: Iterable[Sequence[int]] = (),
              require_donation: bool = False) -> List[Finding]:
    """All findings for one jitted step."""
    out: List[Finding] = []
    if compiled is not None:
        out += find_transfers(compiled, name)
    for dims in forbid_shapes:
        if find_shape(lowered, dims):
            out.append(Finding(
                "dense-kv-materialization", name,
                f"lowering materializes a dense "
                f"{'x'.join(str(d) for d in dims)} KV buffer — the paged "
                f"path must stream pages, not gather them"))
    for dims in forbid_fp32_shapes:
        if find_shape(lowered, dims, dtype="f32"):
            out.append(Finding(
                "fp32-page-materialization", name,
                f"lowering holds a float32 "
                f"{'x'.join(str(d) for d in dims)} page buffer — a "
                f"quantized store must dequantize per block in the "
                f"kernel, never upcast the pool"))
    if require_donation and not has_donation(lowered):
        out.append(Finding(
            "missing-donation", name,
            "declared-donated buffer does not alias in the lowering "
            "(no tf.aliasing_output / jax.buffer_donor marker)"))
    return out


# ---------------------------------------------------------------------------
# The serving steps under lint (mirrors serving/engine.py's jit set)
# ---------------------------------------------------------------------------


def pool_fp32_shapes(cfg, n_pages: int, page_size: int) -> List[tuple]:
    """The float32 twins of the int8 page store
    (``models.model.init_paged_caches``): one layer's pages ``(n_pages,
    page_size, KVH * hd)`` and the whole store ``(L, n_pages, page_size,
    KVH * hd)``."""
    page = (n_pages, page_size, cfg.n_kv_heads * cfg.hd)
    return [page, (cfg.n_layers,) + page]


def serving_steps(cfg=None, compile_steps: bool = True) -> Dict[str, dict]:
    """Build + lower (+ compile) every jitted serving step at the smoke
    config.  Returns ``{name: kwargs-for-lint_step}``.

    Steps and their donation declarations come from
    ``ServingEngine.__init__``; donation is FORCED here (plain ``jax.jit``
    rather than ``jit_step``) so the donation lint checks the aliasing a
    donation-capable backend compiles, even on CPU.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from .. import configs
    from ..dist.sharding import MeshRules
    from ..models import model as M
    from ..serving.steps import (make_decode_step, make_paged_prefill_step,
                                 make_prefill_step)

    cfg = cfg or configs.get_smoke("llama3.2-1b")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    rules = MeshRules()
    params = M.init_params(jax.random.PRNGKey(0), cfg)

    B, T = 2, 8                              # dense prefill batch
    n_pages, page_size, lanes = 16, 8, 8     # paged geometry (max_seq 64)
    dense_kv = (B, lanes * page_size, cfg.n_kv_heads, cfg.hd)

    paged_kv = M.init_paged_caches(cfg, n_pages, page_size)
    paged_kv_q = M.init_paged_caches(cfg, n_pages, page_size,
                                     quantized=True)
    # the int8 pool's fp32 twins, in the store's lane-dense page layout: a
    # quantized step holding either one (a layer's pages or the whole
    # store) has dequantized outside the kernel
    pool_fp32 = pool_fp32_shapes(cfg, n_pages, page_size)
    caches = M.init_caches(cfg, B, lanes * page_size)
    tokens = jnp.zeros((B, T), jnp.int32)
    token = jnp.zeros((B, 1), jnp.int32)
    clen = jnp.ones((B,), jnp.int32)
    pages = jnp.full((B, lanes), -1, jnp.int32)
    chunk_lens = jnp.zeros((B,), jnp.int32)
    src = jnp.zeros((), jnp.int32)

    def copy_page(kv, src, dst):
        return jax.tree.map(lambda x: x.at[:, dst].set(x[:, src]), kv)

    specs: List[Tuple[str, object, tuple, tuple, list, list]] = [
        # (name, fn, args, donate_argnums, forbidden dense shapes,
        #  forbidden fp32 pool shapes)
        ("prefill", make_prefill_step(cfg, mesh, rules),
         (params, {"tokens": tokens}), (), [], []),
        ("decode", make_decode_step(cfg, mesh, rules),
         (params, caches, token, clen), (), [], []),
        ("decode_paged", make_decode_step(cfg, mesh, rules, paged=True),
         (params, paged_kv, token, clen, pages), (1,), [dense_kv], []),
        ("prefill_paged", make_paged_prefill_step(cfg, mesh, rules),
         (params, paged_kv, tokens, clen, chunk_lens, pages), (1,),
         [dense_kv], []),
        # quantized store: same steps over the int8 pool — still no dense
        # gather, and additionally no fp32 page buffer anywhere in the
        # lowering (dequant lives inside the kernel)
        ("decode_paged_quant",
         make_decode_step(cfg, mesh, rules, paged=True),
         (params, paged_kv_q, token, clen, pages), (1,), [dense_kv],
         pool_fp32),
        ("prefill_paged_quant", make_paged_prefill_step(cfg, mesh, rules),
         (params, paged_kv_q, tokens, clen, chunk_lens, pages), (1,),
         [dense_kv], pool_fp32),
        ("copy_page", copy_page, (paged_kv, src, src), (0,), [], []),
    ]

    out: Dict[str, dict] = {}
    for name, fn, args, donate, forbid, forbid_f32 in specs:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        d = {
            "lowered": lowered.as_text(),
            "compiled": (lowered.compile().as_text() if compile_steps
                         else None),
            "forbid_shapes": forbid,
            "forbid_fp32_shapes": forbid_f32,
            "require_donation": bool(donate),
        }
        out[name] = d
    return out


def lint_serving_steps(cfg=None, compile_steps: bool = True) -> List[Finding]:
    """Findings across every jitted serving step (empty = clean)."""
    findings: List[Finding] = []
    for name, kw in serving_steps(cfg, compile_steps=compile_steps).items():
        findings += lint_step(name, **kw)
    return findings
