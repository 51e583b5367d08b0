"""Quantized paged-KV benchmark: the PR-10 acceptance record.

Four sections, every one a CI gate (nonzero exit on loss):

* **bytes** — the economics: per-(page, layer) K+V bytes of the int8
  store vs the bf16 store (must be >= 2x smaller; the fp32 comparison and
  the per-page scale overhead are reported alongside), and the derived
  concurrent-users-per-GB-of-HBM figure at the serving geometry.
* **error** — correctness envelope: the quantized kernels (through the
  autotuned public wrappers) match the quant oracle to float tolerance
  and stay inside the documented attention-output error bound (< 0.05 at
  unit-variance inputs; per-element round trip is <= scale/2) of the
  fp32 oracle.
* **latency** — quantized vs fp32 paged decode / chunk-prefill kernel
  step time on this backend (informational CPU-interpret numbers; the
  committed baseline puts them under the bench-gate bands).
* **zipf** — the PR-9 collision regression, closed: the BENCH_slo Zipf
  key stream replayed against the pool's prefix index (match -> allocate
  -> insert -> release, the engine's admission order) must show a
  full-set collision rate **< 0.05** — the 4-way set-associative index
  vs the 0.47 the direct-mapped index measured.

    PYTHONPATH=src python -m benchmarks.quant            # full, writes JSON
    PYTHONPATH=src python -m benchmarks.quant --smoke    # CI gate
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.smoke import FAILURES, check, timeit
from repro import configs
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.kernels.quant import dequantize_pages, quantize_pages
from repro.models import model as M
from repro.serving.kv_pool import KVPool, page_keys
from repro.serving.loadgen import LoadgenConfig, generate_trace


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: fewer repeats, shorter trace")
    ap.add_argument("--out", default=None)
    return ap.parse_args()


ARGS = _parse()
CFG = configs.get_smoke("llama3.2-1b")

# the BENCH_slo serving geometry (benchmarks/slo.py): the bytes and zipf
# sections measure the SAME pool the SLO engine runs
N_PAGES, PAGE_SIZE, MAX_SEQ, LANES = 128, 8, 64, 8


# ---------------------------------------------------------------------------
# Bytes: page layout economics
# ---------------------------------------------------------------------------


def bench_bytes() -> dict:
    kvh, hd, nl = CFG.n_kv_heads, CFG.hd, CFG.n_layers
    store16 = M.init_paged_caches(CFG, N_PAGES, PAGE_SIZE)
    store8 = M.init_paged_caches(CFG, N_PAGES, PAGE_SIZE, quantized=True)
    kv16 = sum(int(store16[n].nbytes) for n in ("k", "v"))
    kv8 = sum(int(store8[n].nbytes) for n in ("k", "v"))
    scales = sum(int(store8[n].nbytes) for n in ("k_scale", "v_scale"))
    # per (page, layer): K+V content plus (for the int8 store) its scales
    page16 = kv16 // (N_PAGES * nl)
    page8 = kv8 // (N_PAGES * nl)
    page8_scaled = (kv8 + scales) // (N_PAGES * nl)
    ratio = page16 / page8
    check(ratio >= 2.0,
          f"int8 KV bytes/page >= 2x smaller than bf16 "
          f"({page16} -> {page8}, ratio {ratio:.2f})")
    # users per GB of HBM at the serving geometry (whole store + scales)
    per_user_pages = -(-MAX_SEQ // PAGE_SIZE)
    user16 = per_user_pages * nl * page16
    user8 = per_user_pages * nl * page8_scaled
    gb = 1 << 30
    return {
        "page_size": PAGE_SIZE, "kv_heads": kvh, "head_dim": hd,
        "layers": nl,
        "bf16_bytes_per_page_layer": page16,
        "int8_bytes_per_page_layer": page8,
        "int8_scale_bytes_per_page_layer": page8_scaled - page8,
        "fp32_bytes_per_page_layer": page16 * 2,
        "page_bytes_ratio_vs_bf16": round(ratio, 4),
        "page_bytes_ratio_vs_fp32": round(page16 * 2 / page8, 4),
        "store_bytes_ratio_incl_scales": round(kv16 / (kv8 + scales), 4),
        "users_per_gb_hbm_bf16": gb // user16,
        "users_per_gb_hbm_int8": gb // user8,
        "users_per_hbm_byte_gain": round(user16 / user8, 4),
    }


# ---------------------------------------------------------------------------
# Error: quantized kernels inside the documented bound
# ---------------------------------------------------------------------------


def _decode_case(seed, b=8, h=4, kvh=2, hd=32, ps=PAGE_SIZE, lanes=LANES,
                 n_pages=64):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((b, h, hd)), jnp.float32)
    k = jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32)
    v = jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32)
    pi = np.full((b, lanes), -1, np.int32)
    cl = np.zeros((b,), np.int32)
    perm = r.permutation(n_pages)
    off = 0
    for i in range(b):
        used = int(r.integers(1, lanes + 1))
        pi[i, :used] = perm[off:off + used]
        off += used
        cl[i] = int(r.integers((used - 1) * ps + 1, used * ps + 1))
    return q, k, v, jnp.asarray(pi), jnp.asarray(cl)


ERR_BOUND = 0.05      # gated attention-output envelope, unit-variance in
L0 = jnp.int32(0)     # the one layer of the cases' one-layer stores


def _stores(k, v):
    """(n_pages, ps, KVH, hd) pages -> one-layer lane-dense stores: (k, v,
    kq, vq, ks, vs), float and int8 with its scales."""
    kq, ks = map(R.one_layer, quantize_pages(k))
    vq, vs = map(R.one_layer, quantize_pages(v))
    return R.one_layer(k), R.one_layer(v), kq, vq, ks, vs


def bench_error() -> dict:
    worst_vs_fp32 = worst_vs_qref = 0.0
    for seed in (0, 1):
        q, k, v, pi, cl = _decode_case(seed)
        k, v, kq, vq, ks, vs = _stores(k, v)
        out = np.asarray(K.paged_attention_quant(q, kq, vq, ks, vs, L0, pi,
                                                 cl))
        qref = np.asarray(jax.jit(R.paged_attn_quant_ref)(
            q, kq, vq, ks, vs, L0, pi, cl))
        ref32 = np.asarray(jax.jit(R.paged_attn_ref)(q, k, v, L0, pi, cl))
        worst_vs_qref = max(worst_vs_qref,
                            float(np.max(np.abs(out - qref))))
        worst_vs_fp32 = max(worst_vs_fp32,
                            float(np.max(np.abs(out - ref32))))
    # per-element round trip: <= scale/2 by construction
    r = np.random.default_rng(2)
    x = jnp.asarray(r.standard_normal((32, PAGE_SIZE, 2, 32)), jnp.float32)
    xq, xs = quantize_pages(x)
    rt = float(jnp.max(jnp.abs(dequantize_pages(xq, xs) - x)))
    rt_bound = float(jnp.max(xs)) / 2
    check(worst_vs_qref < 1e-5,
          f"quant kernel == quant oracle to float tolerance "
          f"({worst_vs_qref:.2e})")
    check(worst_vs_fp32 < ERR_BOUND,
          f"quant attention within {ERR_BOUND} of fp32 oracle "
          f"({worst_vs_fp32:.4f})")
    check(rt <= rt_bound + 1e-7,
          f"round-trip error <= scale/2 ({rt:.4f} vs {rt_bound:.4f})")
    return {
        "max_err_vs_quant_oracle": worst_vs_qref,
        "max_err_vs_fp32_oracle": round(worst_vs_fp32, 6),
        "err_bound": ERR_BOUND,
        "round_trip_max_err": round(rt, 6),
        "round_trip_bound": round(rt_bound, 6),
        "error_within_bound": worst_vs_fp32 < ERR_BOUND,
    }


# ---------------------------------------------------------------------------
# Latency: quantized vs fp32 kernel step time on this backend
# ---------------------------------------------------------------------------


def bench_latency(smoke: bool) -> dict:
    iters = 3 if smoke else 10
    q, k, v, pi, cl = _decode_case(3)
    k, v, kq, vq, ks, vs = _stores(k, v)
    t16 = timeit(lambda: K.paged_attention(q, k, v, L0, pi, cl)
                 .block_until_ready(), iters)
    t8 = timeit(lambda: K.paged_attention_quant(q, kq, vq, ks, vs, L0, pi,
                                                cl).block_until_ready(),
                iters)
    s = 8
    r = np.random.default_rng(4)
    qc = jnp.asarray(r.standard_normal((4, s, 4, 32)), jnp.float32)
    nl = jnp.minimum(cl[:4], s)
    c16 = timeit(lambda: K.paged_chunk_attention(
        qc, k, v, L0, pi[:4], cl[:4], nl).block_until_ready(), iters)
    c8 = timeit(lambda: K.paged_chunk_attention_quant(
        qc, kq, vq, ks, vs, L0, pi[:4], cl[:4], nl).block_until_ready(),
        iters)
    return {
        "decode_fp32_us": round(t16 * 1e6, 1),
        "decode_quant_us": round(t8 * 1e6, 1),
        "decode_quant_speedup": round(t16 / max(t8, 1e-12), 3),
        "chunk_fp32_us": round(c16 * 1e6, 1),
        "chunk_quant_us": round(c8 * 1e6, 1),
        "chunk_quant_speedup": round(c16 / max(c8, 1e-12), 3),
    }


# ---------------------------------------------------------------------------
# Zipf: the prefix-index collision gate on the BENCH_slo key stream
# ---------------------------------------------------------------------------


def bench_zipf_collisions(smoke: bool) -> dict:
    # the exact BENCH_slo trace configs (benchmarks/slo.py:_trace_cfg)
    cfg = LoadgenConfig(
        duration_s=2.5 if smoke else 8.0,
        base_rps=8.0 if smoke else 6.0,
        burst_factor=5.0,
        burst_period_s=1.25 if smoke else 2.5,
        burst_duty=0.3,
        seed=7,
    )
    trace = generate_trace(cfg)
    pool = KVPool(N_PAGES)
    inserted = 0
    for tr in trace.requests:
        kh, kl, ln = page_keys(tr.prompt, PAGE_SIZE, pad_to=LANES)
        _, n_run, _ = pool.match_prefix(kh, kl, ln)
        # publish the tail the hit run does not cover (admission order:
        # hit lanes ride by reference, fresh lanes allocate + insert)
        n_keys = int(np.sum(ln > 0))
        fresh = list(range(n_run, n_keys))
        pages = pool.allocate(tr.rid, len(fresh)) if fresh else []
        if fresh and not pages:
            continue                     # pool exhausted even post-evict
        lane_pg = np.full((LANES,), -1, np.int32)
        for lane, pg in zip(fresh, pages):
            lane_pg[lane] = pg
        ins = pool.insert_prefix(tr.rid, kh, kl, ln, lane_pg)
        shared = np.asarray([lane_pg[i] for i in range(n_keys)
                             if ins[i]], np.int32)
        inserted += len(shared)
        if len(shared):                  # request done: refs drop to 0,
            pool.release_refs(shared)    # entries stay cached in the map
        pool.reclaim(tr.rid)             # non-converted pages free
    lookups = pool.prefix_lookups
    colls = pool.prefix_collisions
    rate = colls / max(lookups, 1)
    check(lookups >= len(trace.requests),
          f"zipf replay exercised the index ({lookups} lookups)")
    check(rate < 0.05,
          f"set-associative prefix index collision rate < 0.05 on the "
          f"BENCH_slo zipf trace (got {rate:.4f}; direct-mapped measured "
          f"0.47)")
    return {
        "requests": len(trace.requests),
        "prefix_lookups": lookups,
        "prefix_hits": pool.prefix_hits,
        "prefix_collisions": colls,
        "collision_rate": round(rate, 4),
        "collision_rate_ok": rate < 0.05,
        "map_ways": pool.ways,
        "inserted_pages": inserted,
    }


def main() -> int:
    rec = {
        "bench": "quant",
        "mode": "smoke" if ARGS.smoke else "full",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "model": CFG.name,
        "bytes": bench_bytes(),
        "error": bench_error(),
        "latency": bench_latency(ARGS.smoke),
        "zipf": bench_zipf_collisions(ARGS.smoke),
        "failures": FAILURES,
    }
    out = ARGS.out
    if out is None and not ARGS.smoke:
        out = str(Path(__file__).resolve().parents[1] / "BENCH_quant.json")
    if out:
        Path(out).write_text(json.dumps(rec, indent=1))
        print(f"wrote {out}", flush=True)
    print(json.dumps({k: rec[k] for k in ("bytes", "error", "latency",
                                          "zipf")}, indent=1))
    if FAILURES:
        print(f"FAILED: {FAILURES}", file=sys.stderr)
        return 1
    print("quant bench OK", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
