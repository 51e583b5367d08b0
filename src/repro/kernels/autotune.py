"""Autotune sweep for the paged-attention kernels.

The two paged kernels (and their quantized variants) each expose one
performance knob:

* ``paged_attn`` / ``paged_attn_quant`` — ``lanes_per_step``: how many KV
  pages one grid step DMAs into VMEM (the decode kernel's
  pages-per-DMA-lane).  More lanes per step amortizes grid overhead at the
  cost of VMEM footprint.
* ``paged_chunk_attn`` / ``paged_chunk_attn_quant`` — ``block_q``: the
  q-block height of the chunk-prefill kernel (0 = the kernel's built-in
  heuristic, ``_pick_block_q``).

This module sweeps the candidate values per kernel on the CURRENT backend,
verifies every candidate against the jnp oracle in :mod:`repro.kernels.ref`
before timing it (a fast wrong kernel must never win), times the survivors
with ``block_until_ready`` best-of-``repeats``, and writes the winners to
``tuning_table.json`` next to :mod:`repro.kernels.ops`, which reads it at
call time::

    {"paged_attn": {"cpu": {"lanes_per_step": 2}}, ...}

The table is keyed by ``jax.default_backend()``: CPU entries come from the
interpret-mode sweep (Pallas body in Python — a real measurement of this
container's validation path); on a TPU host the same command produces
Mosaic timings (``--mode`` reports which one ran).  A backend absent from
the table silently falls back to the defaults, so committing CPU numbers
never pessimizes TPU and vice versa.

Usage::

    python -m repro.kernels.autotune               # sweep + report
    python -m repro.kernels.autotune --out src/repro/kernels/tuning_table.json
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .ops import _interpret
from .paged_attn import _paged_attn_call, _paged_attn_quant_call
from .paged_chunk_attn import _chunk_attn_call, _chunk_attn_quant_call
from .quant import quantize_pages

__all__ = ["sweep", "run", "mode"]


def mode() -> str:
    """How the kernels execute on this host: ``mosaic`` (compiled, TPU)
    or ``interpret`` (Pallas body in Python — the validation backend)."""
    return "interpret" if _interpret() else "mosaic"


# --------------------------------------------------------------------------
# Case builders: one decode case and one chunk-prefill case at a small but
# representative shape.  Both variants (fp32 / quantized) share the same
# underlying pages so the sweep compares like with like.
# --------------------------------------------------------------------------


def _decode_case(seed: int, *, b: int = 4, h: int = 4, kvh: int = 2,
                 hd: int = 32, ps: int = 8, lanes: int = 8,
                 n_pages: int = 64):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((b, h, hd)), jnp.float32)
    k = jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32)
    v = jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32)
    # each row gets a distinct page run; trailing lanes unused (-1)
    pi = np.full((b, lanes), -1, np.int32)
    cl = np.zeros((b,), np.int32)
    for i in range(b):
        used = int(r.integers(1, lanes + 1))
        pi[i, :used] = r.choice(n_pages, size=used, replace=False)
        cl[i] = int(r.integers((used - 1) * ps + 1, used * ps + 1))
    return q, k, v, jnp.asarray(pi), jnp.asarray(cl)


def _chunk_case(seed: int, *, s: int = 16, **kw):
    q1, k, v, pi, cl = _decode_case(seed, **kw)
    b, h, hd = q1.shape
    r = np.random.default_rng(seed + 1)
    q = jnp.asarray(r.standard_normal((b, s, h, hd)), jnp.float32)
    nl = jnp.asarray(np.minimum(np.asarray(cl), s), jnp.int32)
    return q, k, v, pi, cl, nl


def _time(fn: Callable[[], jax.Array], repeats: int) -> float:
    fn().block_until_ready()          # compile / first interpret pass
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


# --------------------------------------------------------------------------
# The sweep table: kernel -> (knob, candidates, make_timed_fn).  Every
# candidate is verified against the oracle before it is allowed to compete.
# --------------------------------------------------------------------------


def _candidates(seed: int, s: int) -> Dict[str, Tuple[str, List[int], dict]]:
    q, k, v, pi, cl = _decode_case(seed)
    kq, ks = quantize_pages(k)
    vq, vs = quantize_pages(v)
    cq, _, _, _, _, cnl = _chunk_case(seed, s=s)
    dec_ref = ref.paged_attn_ref(q, k, v, pi, cl)
    dec_qref = ref.paged_attn_quant_ref(q, kq, vq, ks, vs, pi, cl)
    chk_ref = ref.paged_chunk_attn_ref(cq, k, v, pi, cl, cnl)
    chk_qref = ref.paged_chunk_attn_quant_ref(cq, kq, vq, ks, vs, pi, cl,
                                              cnl)
    it = _interpret()
    bq_cands = [0] + [d for d in (4, 8, 16) if s % d == 0 and d <= s]
    return {
        "paged_attn": ("lanes_per_step", [1, 2, 4], dict(
            fn=lambda n: _paged_attn_call(q, k, v, pi, cl, interpret=it,
                                          lanes_per_step=n),
            oracle=dec_ref, tol=1e-5)),
        "paged_attn_quant": ("lanes_per_step", [1, 2, 4], dict(
            fn=lambda n: _paged_attn_quant_call(q, kq, vq, ks, vs, pi, cl,
                                                interpret=it,
                                                lanes_per_step=n),
            oracle=dec_qref, tol=1e-5)),
        "paged_chunk_attn": ("block_q", bq_cands, dict(
            fn=lambda n: _chunk_attn_call(cq, k, v, pi, cl, cnl,
                                          interpret=it, block_q=n),
            oracle=chk_ref, tol=1e-5)),
        "paged_chunk_attn_quant": ("block_q", bq_cands, dict(
            fn=lambda n: _chunk_attn_quant_call(cq, kq, vq, ks, vs, pi, cl,
                                                cnl, interpret=it,
                                                block_q=n),
            oracle=chk_qref, tol=1e-5)),
    }


def sweep(seed: int = 0, repeats: int = 3, s: int = 16) -> dict:
    """Run the full sweep on the current backend.  -> report dict::

        {kernel: {"knob": str, "mode": str,
                  "results": {value: seconds | "WRONG"},
                  "best": value}}
    """
    out: dict = {}
    for kernel, (knob, cands, spec) in _candidates(seed, s).items():
        fn, oracle, tol = spec["fn"], spec["oracle"], spec["tol"]
        results: dict = {}
        best_v, best_t = None, float("inf")
        for c in cands:
            got = fn(c)
            if not np.allclose(np.asarray(got), np.asarray(oracle),
                               atol=tol, rtol=tol):
                results[c] = "WRONG"   # disqualified before timing
                continue
            t = _time(lambda c=c: fn(c), repeats)
            results[c] = t
            if t < best_t:
                best_v, best_t = c, t
        out[kernel] = {"knob": knob, "mode": mode(), "results": results,
                       "best": best_v}
    return out


def run(out_path: str | None = None, seed: int = 0, repeats: int = 3,
        s: int = 16) -> dict:
    """Sweep and (optionally) merge the winners into a tuning table file.

    Existing entries for OTHER backends are preserved — a CPU sweep never
    clobbers committed TPU numbers."""
    report = sweep(seed=seed, repeats=repeats, s=s)
    if out_path:
        backend = jax.default_backend()
        try:
            table = json.loads(open(out_path).read())
        except (OSError, ValueError):
            table = {}
        for kernel, r in report.items():
            if r["best"] is None:
                continue
            table.setdefault(kernel, {}).setdefault(backend, {})[
                r["knob"]] = r["best"]
        with open(out_path, "w") as f:
            json.dump(table, f, indent=2, sort_keys=True)
            f.write("\n")
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="tuning table to merge winners into")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk width for the block_q sweep")
    args = ap.parse_args()
    report = run(args.out, seed=args.seed, repeats=args.repeats,
                 s=args.chunk)
    print(f"backend={jax.default_backend()} mode={mode()}")
    for kernel, r in report.items():
        print(f"  {kernel} ({r['knob']}):")
        for c, t in r["results"].items():
            mark = " <- best" if c == r["best"] else ""
            val = t if t == "WRONG" else f"{t * 1e3:8.2f} ms"
            print(f"    {c:>3}: {val}{mark}")
    if args.out:
        print(f"wrote winners to {args.out}")


if __name__ == "__main__":
    main()
