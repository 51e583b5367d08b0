"""Streaming chunk-prefill + prefix-cache benchmark: the PR-5 acceptance
record.

Sections (all but timing double as CI smoke gates — exit nonzero on any
mismatch or lost guarantee):

* ``correctness`` — the streaming chunk-prefill kernel vs the
  ``kernels/ref.py`` oracle, BIT-exact (same (row, q-block, page) walk,
  both under jit), plus allclose against the PR-4 dense gather.
* ``materialization`` — the lowered streamed step contains NO dense
  ``(B, lanes * page_size, KVH, hd)`` KV buffer, while the dense
  formulation's lowering provably does (the HLO-text check that the
  streaming claim is real, not a comment).
* ``transfers`` — the chunk-attention call with device-resident operands
  runs under ``jax.transfer_guard("disallow")`` — the streamed prefill
  moves zero bytes of KV between host and device.
* ``dedup`` — the prefix hit-rate sweep: identical scheduler workloads at
  0 / 50 / 90% shared prompts, prefix cache on vs off.  Gates: >= 2x
  page-allocation reduction at 90% shared traffic, and refcounts balance
  to zero after every drain.
* ``timing`` (full mode) — streamed vs dense chunk-attention wall time
  across chunk widths.

    PYTHONPATH=src python -m benchmarks.prefill            # full
    PYTHONPATH=src python -m benchmarks.prefill --smoke    # CI gate
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: no timing sweep")
    ap.add_argument("--tokens", type=int, default=4,
                    help="generated tokens per request in the dedup sweep")
    ap.add_argument("--out", default=None)
    return ap.parse_args()


ARGS = _parse()

import jax                                                       # noqa: E402
import jax.numpy as jnp                                          # noqa: E402
import numpy as np                                               # noqa: E402
from jax.sharding import Mesh                                    # noqa: E402

from benchmarks.smoke import FAILURES, check, timeit             # noqa: E402
from repro import configs                                        # noqa: E402
from repro.dist.sharding import MeshRules                        # noqa: E402
from repro.kernels import ops as K                               # noqa: E402
from repro.kernels import ref as R                               # noqa: E402
from repro.models import model as M                              # noqa: E402
from repro.serving.engine import Request, ServingEngine          # noqa: E402
from repro.serving.scheduler import SchedulerConfig              # noqa: E402

CFG = configs.get_smoke("llama3.2-1b")
PARAMS = M.init_params(jax.random.PRNGKey(0), CFG)
RULES = MeshRules()


def mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _chunk_case(rng, b=4, s=8, h=8, kvh=2, hd=16, n_pages=64, ps=4,
                lanes=8):
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, kvh, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, kvh, hd)), jnp.float32)
    page_idx = np.full((b, lanes), -1, np.int32)
    cache_len = np.zeros((b,), np.int32)
    new_lens = np.zeros((b,), np.int32)
    perm = rng.permutation(n_pages)
    off = 0
    for i in range(b - 1):                 # last row stays fully padded
        nl = int(rng.integers(1, s + 1))
        clen = int(rng.integers(nl, lanes * ps + 1))
        npg = -(-clen // ps)
        page_idx[i, :npg] = perm[off:off + npg]
        off += npg
        cache_len[i] = clen
        new_lens[i] = nl
    # a one-layer store in the store's lane-dense layout, and its layer
    return (q, R.one_layer(kp), R.one_layer(vp), jnp.int32(0)) + tuple(
        map(jnp.asarray, (page_idx, cache_len, new_lens)))


# ---------------------------------------------------------------------------
# Sections
# ---------------------------------------------------------------------------


def bench_correctness() -> dict:
    """Streaming kernel vs oracle (the CI smoke gate)."""
    rng = np.random.default_rng(0)
    q, kp, vp, ly, pi, cl, nl = _chunk_case(rng)
    out_k = np.asarray(K.paged_chunk_attention(q, kp, vp, ly, pi, cl, nl))
    out_r = np.asarray(jax.jit(R.paged_chunk_attn_ref)(q, kp, vp, ly, pi,
                                                       cl, nl))
    check(np.array_equal(out_k, out_r),
          "paged_chunk_attention == paged_chunk_attn_ref (bit-exact)")
    dense = np.asarray(jax.jit(R.paged_chunk_dense_ref)(q, kp, vp, ly, pi,
                                                        cl, nl))
    check(bool(np.allclose(out_k, dense, atol=1e-5)),
          "paged_chunk_attention ~= dense gather formulation")
    check(np.array_equal(out_k[-1], np.zeros_like(out_k[-1])),
          "fully padded row emits zeros")
    return {"verified": not FAILURES}


def bench_materialization() -> dict:
    """The streaming claim, checked against the LOWERED programs via the
    shared ``analysis.lint_hlo`` shape finder: the dense formulation's HLO
    holds a (B, lanes * ps, KVH, hd) gathered KV buffer; the streamed
    kernel's HLO must not."""
    from repro.analysis import lint_hlo as L
    rng = np.random.default_rng(1)
    q, kp, vp, ly, pi, cl, nl = _chunk_case(rng)
    b, lanes = pi.shape
    hd = q.shape[-1]
    _, _, ps, kv_lanes = kp.shape
    dense_kv = (b, lanes * ps, kv_lanes // hd, hd)
    dense_hlo = jax.jit(R.paged_chunk_dense_ref).lower(
        q, kp, vp, ly, pi, cl, nl).as_text()
    streamed_hlo = jax.jit(K.paged_chunk_attention).lower(
        q, kp, vp, ly, pi, cl, nl).as_text()
    check(L.find_shape(dense_hlo, dense_kv),
          f"dense path materializes a {dense_kv} KV buffer (sanity)")
    findings = L.lint_step("paged_chunk_attention", streamed_hlo,
                           forbid_shapes=[dense_kv])
    check(not findings,
          f"streamed path lowers WITHOUT any {dense_kv} buffer "
          + "; ".join(str(f) for f in findings))
    return {"dense_buffer": "x".join(map(str, dense_kv)),
            "dense_hlo_bytes": len(dense_hlo),
            "streamed_hlo_bytes": len(streamed_hlo),
            "streamed_materializes_dense_kv": bool(findings)}


def bench_transfers() -> dict:
    """Chunk attention with device-resident operands moves zero bytes of
    KV between host and device."""
    rng = np.random.default_rng(2)
    q, kp, vp, ly, pi, cl, nl = _chunk_case(rng)

    def step():
        K.paged_chunk_attention(q, kp, vp, ly, pi, cl,
                                nl).block_until_ready()

    step()                                 # warmup / compile
    guard_ok = True
    try:
        with jax.transfer_guard("disallow"):
            step()
    except Exception as e:                 # pragma: no cover
        guard_ok = False
        print(f"  transfer_guard tripped: {e}", flush=True)
    check(guard_ok, "streamed chunk attention runs under "
                    "jax.transfer_guard('disallow')")
    return {"chunk_attn_transfers": 0 if guard_ok else -1,
            "guard_disallow_ok": guard_ok}


def _run_workload(shared_frac: float, n_reqs: int, max_new: int,
                  prefix_cache: bool) -> dict:
    """One scheduler run: ``shared_frac`` of the requests use one common
    prompt (system-prompt-heavy traffic), the rest are unique.  The first
    shared request runs alone to warm the cache (its pages stay cached-
    free after drain), then everything else arrives at once."""
    sc = SchedulerConfig(max_slots=4, page_size=4, max_seq=32,
                         prefill_chunk=8, prefill_rows=2, token_budget=16,
                         prefix_cache=prefix_cache)
    eng = ServingEngine(CFG, PARAMS, mesh=mesh1(), rules=RULES,
                        n_pages=256, scheduler=sc)
    eng.start()
    # a long common prefix (the system-prompt shape): 26 tokens = 6 full
    # pages + a partial tail the sharers copy-on-write
    base = np.arange(1, 27, dtype=np.int32)
    n_shared = round(shared_frac * n_reqs)
    reqs = []
    for i in range(n_reqs):
        if i < n_shared:
            prompt = base
        else:
            prompt = (base + 29 * (i + 1)) % 199 + 1   # unique content
        reqs.append(Request(rid=i, prompt=prompt, max_new=max_new))
    if n_shared:
        eng.submit(reqs[0])
        assert reqs[0].done.wait(timeout=600)
    for r in reqs[1 if n_shared else 0:]:
        eng.submit(r)
    for r in reqs:
        assert r.done.wait(timeout=600)
    eng.stop()
    st = eng.lock_stats()
    pool = st["kv_pool"]
    lookups = max(pool.get("prefix_lookups", 0), 1)
    return {"prefix_cache": prefix_cache,
            "pages_charged": st["engine"]["pages_charged"],
            "pages_saved": st["engine"]["pages_saved"],
            "cow_copies": st["engine"]["cow_copies"],
            "cached_tokens": st["engine"]["cached_tokens"],
            "hit_rate": round(pool.get("prefix_hits", 0) / lookups, 3),
            "refcount_total_after_drain": pool["refcount_total"],
            "free_after_drain": pool["free"],
            "n_pages": pool["n_pages"]}


def bench_dedup(max_new: int) -> dict:
    """Prefix hit-rate sweep at 0 / 50 / 90% shared prompts; the
    acceptance gates ride on the 90% point."""
    n_reqs = 10
    sweep = {}
    for frac in (0.0, 0.5, 0.9):
        on = _run_workload(frac, n_reqs, max_new, prefix_cache=True)
        check(on["refcount_total_after_drain"] == 0,
              f"refcounts balance to zero after drain ({frac:.0%} shared)")
        check(on["free_after_drain"] == on["n_pages"],
              f"all pages returned after drain ({frac:.0%} shared)")
        sweep[f"shared={frac:.0%}"] = on
    off = _run_workload(0.9, n_reqs, max_new, prefix_cache=False)
    sweep["shared=90%_cache_off"] = off
    on90 = sweep["shared=90%"]
    ratio = off["pages_charged"] / max(on90["pages_charged"], 1)
    check(ratio >= 2.0,
          f"page allocations reduced >= 2x at 90% shared traffic "
          f"({off['pages_charged']} -> {on90['pages_charged']}, "
          f"{ratio:.2f}x)")
    check(on90["hit_rate"] > sweep["shared=0%"]["hit_rate"],
          "hit rate rises with shared traffic")
    sweep["alloc_reduction_90pct"] = round(ratio, 2)
    return sweep


def bench_timing() -> dict:
    """Streamed vs dense chunk-attention wall time (full mode only).

    On non-TPU backends the Pallas kernel executes in interpret mode (the
    kernel body runs in Python), so absolute times there measure the
    validation path, not the Mosaic compile — the load-bearing acceptance
    signals are the bit-exactness and no-materialization gates above."""
    out = {"note": ("interpret-mode timings; TPU timings require the "
                    "Mosaic backend" if jax.default_backend() != "tpu"
                    else "compiled Mosaic timings")}
    rng = np.random.default_rng(3)
    dense = jax.jit(R.paged_chunk_dense_ref)
    for s, lanes in ((8, 16), (32, 16), (64, 32)):
        q, kp, vp, ly, pi, cl, nl = _chunk_case(
            rng, b=8, s=s, h=8, kvh=2, hd=32, n_pages=8 * lanes + 8,
            ps=8, lanes=lanes)

        def run_stream():
            K.paged_chunk_attention(q, kp, vp, ly, pi, cl,
                                    nl).block_until_ready()

        def run_dense():
            dense(q, kp, vp, ly, pi, cl, nl).block_until_ready()

        out[f"S={s},lanes={lanes}"] = {
            "streamed_us": round(timeit(run_stream, 20) * 1e6, 1),
            "dense_us": round(timeit(run_dense, 20) * 1e6, 1)}
    return out


def main() -> int:
    smoke = ARGS.smoke
    rec = {
        "bench": "prefill",
        "mode": "smoke" if smoke else "full",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "model": CFG.name,
        "correctness": bench_correctness(),
        "materialization": bench_materialization(),
        "transfers": bench_transfers(),
        "dedup": bench_dedup(ARGS.tokens),
        "failures": FAILURES,
    }
    if not smoke:
        rec["timing"] = bench_timing()
    out = ARGS.out
    if out is None and not smoke:
        out = str(Path(__file__).resolve().parents[1]
                  / "BENCH_prefill.json")
    if out:
        Path(out).write_text(json.dumps(rec, indent=1))
        print(f"wrote {out}", flush=True)
    print(json.dumps({k: rec[k] for k in ("materialization", "dedup")},
                     indent=1))
    if FAILURES:
        print(f"FAILED: {FAILURES}", file=sys.stderr)
        return 1
    print("prefill bench OK", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
