"""Scheduler + paged-attention benchmark: the PR-4 acceptance record.

Sections (all but throughput double as CI smoke gates — exit nonzero on
any mismatch or lost guarantee):

* ``correctness`` — the gather-by-page decode kernel vs the ``kernels/
  ref.py`` oracle, BIT-exact (same page-walk order, both under jit), plus
  allclose against full-softmax attention over densely gathered pages.
* ``equivalence`` — a scheduler-driven ``ServingEngine`` run (paged data
  plane, chunked prefill, eviction-capable) vs the pre-scheduler dense-
  cache decode loop: token-for-token identical output.
* ``transfers`` — the per-step lease batch (KV stripe leases + model-epoch
  lease, acquire AND release) runs under ``jax.transfer_guard("disallow")``
  — zero host transfers on the lease fast path.
* ``mesh2d`` — a scheduler-driven run completes on the 2D dry-run
  topology's ("pod", "data", "model") axis layout (full mode: 8 fake
  devices so the decode step's shard_map path actually partitions the
  batch; smoke: 1-device axes).
* ``throughput`` (full mode) — tokens/s and p50/p99 per-token decode
  latency vs the pre-scheduler handler engine, plus the admission
  watermark sweep (max_slots = 1..8, the concurrency-restriction knob).

    PYTHONPATH=src python -m benchmarks.scheduler            # full
    PYTHONPATH=src python -m benchmarks.scheduler --smoke    # CI gate
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: 1-device meshes, no timing sweep")
    ap.add_argument("--tokens", type=int, default=8,
                    help="generated tokens per request")
    ap.add_argument("--out", default=None)
    return ap.parse_args()


ARGS = _parse()
if not ARGS.smoke:
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

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
from repro.serving.steps import make_decode_step                 # noqa: E402

CFG = configs.get_smoke("llama3.2-1b")
PARAMS = M.init_params(jax.random.PRNGKey(0), CFG)
RULES = MeshRules()


def mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def mesh2d(smoke: bool):
    devs = np.array(jax.devices())
    if smoke or len(devs) < 8:
        return Mesh(devs[:1].reshape(1, 1, 1), ("pod", "data", "model"))
    # (2, 2, 2): the data axes' product (4) divides max_slots, so the
    # paged decode step's shard_map path genuinely partitions the batch
    return Mesh(devs[:8].reshape(2, 2, 2), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# Sections
# ---------------------------------------------------------------------------


def bench_correctness() -> dict:
    """Paged-attention kernel vs oracle (the CI smoke gate)."""
    rng = np.random.default_rng(0)
    b, h, kvh, hd, n_pages, ps, lanes = 6, 8, 2, 16, 64, 8, 5
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, kvh, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, kvh, hd)), jnp.float32)
    page_idx = np.full((b, lanes), -1, np.int32)
    cache_len = np.zeros((b,), np.int32)
    perm = rng.permutation(n_pages)
    off = 0
    for i in range(b):
        npg = int(rng.integers(1, lanes + 1))
        page_idx[i, :npg] = perm[off:off + npg]
        off += npg
        cache_len[i] = int(rng.integers(1, npg * ps + 1))
    cache_len[2] = 0
    pi, cl = jnp.asarray(page_idx), jnp.asarray(cache_len)
    store = R.one_layer(kp), R.one_layer(vp), jnp.int32(0)
    out_k = np.asarray(K.paged_attention(q, *store, pi, cl))
    out_r = np.asarray(jax.jit(R.paged_attn_ref)(q, *store, pi, cl))
    check(np.array_equal(out_k, out_r),
          "paged_attention == paged_attn_ref (bit-exact)")
    check(np.array_equal(out_k[2], np.zeros_like(out_k[2])),
          "inactive slot (cache_len 0) emits zeros")

    from repro.models.common import decode_attention
    kd = np.zeros((b, lanes * ps, kvh, hd), np.float32)
    vd = np.zeros((b, lanes * ps, kvh, hd), np.float32)
    for i in range(b):
        for p in range(lanes):
            if page_idx[i, p] >= 0:
                kd[i, p * ps:(p + 1) * ps] = np.asarray(kp)[page_idx[i, p]]
                vd[i, p * ps:(p + 1) * ps] = np.asarray(vp)[page_idx[i, p]]
    live = cache_len > 0
    dense = np.asarray(decode_attention(
        q[:, None], jnp.asarray(kd), jnp.asarray(vd),
        jnp.asarray(np.maximum(cache_len, 1))))[:, 0]
    check(bool(np.allclose(out_k[live], dense[live], atol=1e-5)),
          "paged_attention ~= dense full-softmax attention")
    return {"verified": not FAILURES}


def _dense_reference(prompt: np.ndarray, max_new: int):
    """The pre-scheduler data plane: dense caches, token-by-token."""
    mesh = mesh1()
    decode = jax.jit(make_decode_step(CFG, mesh, RULES))
    caches = M.init_caches(CFG, 1, 64, dtype=jnp.bfloat16)
    s = len(prompt)
    out = []
    cur = jnp.asarray(prompt[:1][None])
    for step in range(s - 1 + max_new):
        clen = jnp.full((1,), step + 1, jnp.int32)
        nxt, _, caches = decode(PARAMS, caches, cur, clen)
        if step + 1 < s:
            cur = jnp.asarray(prompt[step + 1:step + 2][None])
        else:
            cur = nxt
            out.append(int(np.asarray(nxt)[0, 0]))
    return out


def _run_sched_engine(mesh, prompts, max_new, sched_cfg, n_pages=128,
                      **start_kw):
    eng = ServingEngine(CFG, PARAMS, mesh=mesh, rules=RULES,
                        n_pages=n_pages, scheduler=sched_cfg)
    eng.start(**start_kw)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    for r in reqs:
        assert r.done.wait(timeout=600), "request timed out"
    wall = time.perf_counter() - t0
    eng.stop()
    return eng, [list(r.out) for r in reqs], wall


def bench_equivalence(max_new: int) -> dict:
    """Scheduler-driven paged decode == dense decode, token for token
    (the paged-vs-dense CI equivalence gate)."""
    prompts = [np.arange(1, 7, dtype=np.int32) + 2 * i for i in range(3)]
    want = [_dense_reference(p, max_new) for p in prompts]
    sc = SchedulerConfig(max_slots=4, page_size=8, max_seq=64,
                         prefill_chunk=8, prefill_rows=2, token_budget=16)
    eng, got, _ = _run_sched_engine(mesh1(), prompts, max_new, sc)
    check(got == want, "scheduler paged decode == dense decode "
                       "(token-for-token)")
    check(eng.kv_pool.free_count() == 128, "all pages reclaimed")
    return {"requests": len(prompts), "max_new": max_new,
            "match": got == want}


def bench_transfers() -> dict:
    """The whole step's lease batch — KV stripe leases + model-epoch lease,
    both directions — under jax.transfer_guard('disallow')."""
    sc = SchedulerConfig(max_slots=4, page_size=8, max_seq=64)
    eng = ServingEngine(CFG, PARAMS, mesh=mesh1(), rules=RULES,
                        n_pages=128, scheduler=sc)
    rid_dev = jnp.arange(sc.max_slots, dtype=jnp.int32)

    def lease_roundtrip():
        ptok, _ = eng.pages.read_batch(rid_dev)
        try:
            rtok, _, _ = eng.store.read_batch(rid_dev)
            eng.store.done_read_batch(rtok, rid_dev)
        finally:
            eng.pages.done_read_batch(ptok)

    lease_roundtrip()                      # warmup / compile / rearm
    guard_ok = True
    try:
        with jax.transfer_guard("disallow"):
            lease_roundtrip()
    except Exception as e:                 # pragma: no cover
        guard_ok = False
        print(f"  transfer_guard tripped: {e}", flush=True)
    check(guard_ok, "step lease batch runs under "
                    "jax.transfer_guard('disallow')")

    # static counterpart: the decode step dispatched inside the lease
    # window compiles to HLO with zero host<->device transfer ops
    from repro.analysis import lint_hlo as L
    cur = jnp.zeros((sc.max_slots, 1), jnp.int32)
    clen = jnp.ones((sc.max_slots,), jnp.int32)
    ptbl = jnp.full((sc.max_slots, sc.lanes), -1, jnp.int32)
    compiled = eng._decode_paged.lower(
        PARAMS, eng._pages_kv, cur, clen, ptbl).compile().as_text()
    xfers = L.find_transfers(compiled, "decode_paged")
    check(not xfers, "lease-held decode step compiles with zero "
                     "host transfers " + "; ".join(str(f) for f in xfers))

    pair_s = timeit(lease_roundtrip, 8)
    return {"lease_fast_path_transfers": 0 if guard_ok else -1,
            "guard_disallow_ok": guard_ok,
            "decode_step_hlo_transfers": len(xfers),
            "lease_roundtrip_us": round(pair_s * 1e6, 2)}


def bench_mesh2d(smoke: bool, max_new: int) -> dict:
    """Scheduler-driven decode on the 2D dry-run topology's axis layout."""
    mesh = mesh2d(smoke)
    prompts = [np.arange(1, 7, dtype=np.int32) + i for i in range(4)]
    want = [_dense_reference(p, max_new) for p in prompts]
    sc = SchedulerConfig(max_slots=4, page_size=8, max_seq=64,
                         prefill_chunk=8, prefill_rows=2, token_budget=16)
    eng, got, wall = _run_sched_engine(mesh, prompts, max_new, sc,
                                       swap_period_s=0.1,
                                       perturb=lambda p: p)
    check(got == want, f"2D-mesh scheduler run matches dense "
                       f"(mesh {dict(mesh.shape)})")
    st = eng.lock_stats()
    nb = mesh.shape["pod"] * mesh.shape["data"]
    return {"mesh": dict(mesh.shape), "match": got == want,
            "batch_sharded": nb > 1 and sc.max_slots % nb == 0,
            "weight_swaps": st["engine"]["weight_swaps"],
            "decode_steps": st["engine"]["decode_steps"],
            "wall_s": round(wall, 3)}


def _latency_stats(eng) -> dict:
    # warmup exclusion is built into the engine (obs_warmup_steps)
    h = eng.metrics.histogram("engine.step_ns")
    if not h.count:
        return {}
    return {"decode_p50_us": round(h.quantile(0.50) / 1e3, 2),
            "decode_p99_us": round(h.quantile(0.99) / 1e3, 2)}


def bench_throughput(max_new: int) -> dict:
    """tokens/s + per-token latency: scheduler vs pre-scheduler engine,
    and the admission (concurrency-restriction) watermark sweep."""
    prompts = [np.arange(1, 9, dtype=np.int32) + i for i in range(8)]

    # pre-scheduler handler engine (dense caches, per-handler batches)
    eng = ServingEngine(CFG, PARAMS, mesh=mesh1(), rules=RULES,
                        handlers=2, max_seq=64, slots_per_handler=4,
                        n_pages=128)
    eng.start()
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    for r in reqs:
        assert r.done.wait(timeout=600)
    legacy_wall = time.perf_counter() - t0
    eng.stop()
    legacy_toks = sum(len(r.out) for r in reqs)

    sweep = {}
    for slots in (1, 2, 4, 8):
        sc = SchedulerConfig(max_slots=slots, page_size=8, max_seq=64,
                             prefill_chunk=8, prefill_rows=2,
                             token_budget=16)
        e2, outs, wall = _run_sched_engine(mesh1(), prompts, max_new, sc)
        toks = sum(len(o) for o in outs)
        sweep[f"max_slots={slots}"] = {
            "tokens_per_s": round(toks / wall, 2),
            "wall_s": round(wall, 3),
            "evictions": e2.scheduler.evictions,
            # post-dedup admission charge (PR 5): pages actually allocated
            # after prefix-cache hits — comparable across PRs even as the
            # dedup changes how many pages a request pays for
            "pages_charged": e2.stats.pages_charged,
            "pages_saved": e2.stats.pages_saved,
            **_latency_stats(e2)}
    return {"legacy_engine": {"tokens_per_s":
                              round(legacy_toks / legacy_wall, 2),
                              "wall_s": round(legacy_wall, 3)},
            "admission_sweep": sweep}


def main() -> int:
    smoke = ARGS.smoke
    max_new = ARGS.tokens
    rec = {
        "bench": "scheduler",
        "mode": "smoke" if smoke else "full",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "jax": jax.__version__,
        "model": CFG.name,
        "correctness": bench_correctness(),
        "equivalence": bench_equivalence(max_new),
        "transfers": bench_transfers(),
        "mesh2d": bench_mesh2d(smoke, max_new),
        "failures": FAILURES,
    }
    if not smoke:
        rec["throughput"] = bench_throughput(max_new)
    out = ARGS.out
    if out is None and not smoke:
        out = str(Path(__file__).resolve().parents[1]
                  / "BENCH_scheduler.json")
    if out:
        Path(out).write_text(json.dumps(rec, indent=1))
        print(f"wrote {out}", flush=True)
    print(json.dumps({k: rec[k] for k in ("equivalence", "transfers",
                                          "mesh2d")}, indent=1))
    if FAILURES:
        print(f"FAILED: {FAILURES}", file=sys.stderr)
        return 1
    print("scheduler bench OK", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
