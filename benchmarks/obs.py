"""Observability overhead benchmark: the PR-8 acceptance record.

The obs layer (``repro.obs``) rides every hot path in the repo — reader
publishes, decode ticks, page allocs — so its cost is a first-class
guarantee, measured and gated here exactly like the lock-protocol
guarantees are gated in the other benches.  Sections (all double as CI
smoke gates — exit nonzero on any lost guarantee):

* ``emit_cost`` — microbenchmark of the emit site itself.  Disabled, a
  site is ONE branch (``if _TR.enabled:``): its cost must be noise
  (< 250 ns even under CPython attribute-lookup pessimism).  Enabled,
  one ring emit must stay under 10 µs.
* ``tick_cost`` — the scheduler's per-tick instrumentation alone (the
  clock reads, the nine spans of a decode tick and its clock anchor),
  with no profiler session active and with one: gated < 100 µs a tick.
* ``step_overhead`` — the same scheduler-engine decode workload run
  twice, tracing off then on.  The gated number is the per-step tracing
  cost (measured events/step x measured emit cost) as a fraction of the
  untraced decode p50: **< 2%**.  The direct p50 delta is recorded too
  (informational — on shared CPU it is noise-dominated) with a wide
  sanity band.
* ``chrome_hotswap`` — hot-swap under traffic with tracing enabled; the
  merged timeline must export to Chrome-trace JSON that passes
  :func:`repro.obs.chrome.validate` (balanced async spans, schema-clean)
  and survives a ``json`` round-trip, and every request must derive a
  complete lifecycle (admit -> first token -> done, TTFT defined).
* ``zero_sync`` — tracing ENABLED, the registry acquire/release pair
  still runs under ``jax.transfer_guard("disallow")``: tracing adds no
  transfer to the lease fast path.

    PYTHONPATH=src python -m benchmarks.obs            # full, writes JSON
    PYTHONPATH=src python -m benchmarks.obs --smoke    # CI gate
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from benchmarks.smoke import FAILURES, check
from repro import configs
from repro.core import registry as REG
from repro.dist.sharding import MeshRules
from repro.models import model as M
from repro.obs import TRACER, clock_anchor
from repro.obs.chrome import to_chrome, validate
from repro.obs.trace import Tracer, derive_requests
from repro.serving.engine import EngineConfig, Request, ServingEngine
from repro.serving.scheduler import SchedulerConfig
from repro.serving.steps import make_decode_step


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: fewer requests/iterations")
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--out", default=None)
    return ap.parse_args()


ARGS = _parse()
CFG = configs.get_smoke("llama3.2-1b")
PARAMS = M.init_params(jax.random.PRNGKey(0), CFG)
RULES = MeshRules()


def mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def _dense_reference(prompt: np.ndarray, max_new: int):
    decode = jax.jit(make_decode_step(CFG, mesh1(), RULES))
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


def _engine(n_pages=128):
    sc = SchedulerConfig(max_slots=4, page_size=8, max_seq=64,
                         prefill_chunk=8, prefill_rows=2, token_budget=16)
    ecfg = EngineConfig(idle_poll_s=0.01)
    return ServingEngine(CFG, PARAMS, mesh=mesh1(), rules=RULES,
                         n_pages=n_pages, scheduler=sc, engine_cfg=ecfg)


def _serve(eng, prompts, max_new, mid=None):
    eng.start()
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    if mid is not None:
        mid()
    done = [r.done.wait(timeout=600) for r in reqs]
    eng.stop()
    dropped = sum(1 for r, ok in zip(reqs, done)
                  if not ok or r.out is None or len(r.out) != max_new)
    return [list(r.out) if r.out is not None else [] for r in reqs], dropped


# ---------------------------------------------------------------------------
# Sections
# ---------------------------------------------------------------------------


def bench_emit_cost(n: int) -> dict:
    tr = Tracer(capacity=4096)          # private: global TRACER untouched
    r = range(n)

    def timed(fn) -> float:
        t0 = time.perf_counter_ns()
        fn()
        return (time.perf_counter_ns() - t0) / n

    def empty():
        for _ in r:
            pass

    def disabled_site():
        for _ in r:
            if tr.enabled:
                tr.emit("lock", "publish", batch=8)

    def enabled_emit():
        for _ in r:
            tr.emit("lock", "publish", batch=8)

    def enabled_span():
        for _ in r:
            tr.emit_span("engine", "decode_step", 0, dur_ns=100, batch=4)

    # best-of-3 per shape: the min is the least scheduler-perturbed run
    base = min(timed(empty) for _ in range(3))
    tr.disable()
    disabled = min(timed(disabled_site) for _ in range(3))
    tr.enable()
    emit = min(timed(enabled_emit) for _ in range(3))
    span = min(timed(enabled_span) for _ in range(3))
    tr.disable()

    disabled_site_ns = max(disabled - base, 0.0)
    rec = {"iters": n,
           "loop_baseline_ns": round(base, 1),
           "disabled_site_ns": round(disabled_site_ns, 1),
           "enabled_emit_ns": round(emit, 1),
           "enabled_span_ns": round(span, 1)}
    check(disabled_site_ns < 250.0,
          f"disabled emit site is one branch, noise-level "
          f"(got {disabled_site_ns:.0f} ns)")
    check(emit < 10_000.0,
          f"enabled emit < 10 us (got {emit:.0f} ns)")
    return rec


def _traced_run(prompts, want, max_new, traced: bool):
    TRACER.clear()
    (TRACER.enable if traced else TRACER.disable)()
    try:
        eng = _engine()
        got, dropped = _serve(eng, prompts, max_new)
        h = eng.metrics.histogram("engine.step_ns")
        p50 = h.quantile(0.50) if h.count else 0.0
        steps = eng.stats.decode_steps
        events = len(TRACER.snapshot()) if traced else 0
        check(dropped == 0 and got == want,
              f"{'traced' if traced else 'untraced'} run: 0 dropped, "
              f"tokens == dense reference")
        return p50, steps, events
    finally:
        TRACER.disable()


TICK_SPANS = ("sched_tick", "tick_admit", "tick_grow", "decode_step",
              "lease_acquire", "step_dispatch", "lease_release",
              "step_readback", "tick_finish")


def bench_tick_cost(n: int) -> dict:
    """Per decode tick: the five clock reads the engine takes for its
    phases, the tick's spans and one clock anchor, on a private tracer;
    then the same inside a ``jax.profiler`` session (where the anchor is
    recorded)."""
    tr = Tracer(capacity=1 << 16)
    tr.enable()

    def per_tick_us() -> float:
        t0 = time.perf_counter_ns()
        for i in range(n):
            marks = [time.monotonic_ns() for _ in range(5)]
            for name in TICK_SPANS:
                tr.emit_span("engine", name, marks[0], dur_ns=1, tick=i)
            clock_anchor()
        return (time.perf_counter_ns() - t0) / n / 1e3

    off = per_tick_us()
    d = tempfile.mkdtemp(prefix="obs-tick-")
    jax.profiler.start_trace(d)
    try:
        on = per_tick_us()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    check(off < 100 and on < 100,
          f"tick instrumentation {off:.1f} µs, {on:.1f} µs profiled "
          f"(< 100 µs a tick)")
    return {"ticks": n, "spans_per_tick": len(TICK_SPANS),
            "per_tick_us": round(off, 2),
            "per_tick_profiled_us": round(on, 2)}


def bench_step_overhead(max_new: int, n_req: int, emit_ns: float) -> dict:
    prompts = [np.arange(1, 8, dtype=np.int32) + i for i in range(n_req)]
    want = [_dense_reference(p, max_new) for p in prompts]

    p50_off, steps_off, _ = _traced_run(prompts, want, max_new, False)
    p50_on, steps_on, events = _traced_run(prompts, want, max_new, True)

    events_per_step = events / max(steps_on, 1)
    # the gated number: measured emits/step x measured per-emit cost,
    # as a fraction of the untraced decode p50 — deterministic where the
    # direct A/B delta is CPU-noise-dominated
    overhead_pct = (events_per_step * emit_ns) / max(p50_off, 1.0) * 100.0
    direct_pct = (p50_on - p50_off) / max(p50_off, 1.0) * 100.0
    rec = {"decode_steps": steps_off,
           "events_per_step": round(events_per_step, 2),
           "untraced_p50_us": round(p50_off / 1e3, 2),
           "traced_p50_us": round(p50_on / 1e3, 2),
           "overhead_pct": round(overhead_pct, 3),
           "direct_p50_delta_pct": round(direct_pct, 2)}
    check(overhead_pct < 2.0,
          f"tracing overhead < 2% of step latency "
          f"(got {overhead_pct:.3f}%)")
    check(direct_pct < 25.0,
          f"traced p50 within the CPU-noise sanity band "
          f"(got {direct_pct:+.1f}%)")
    return rec


def bench_chrome_hotswap(max_new: int, n_req: int) -> dict:
    prompts = [np.arange(1, 8, dtype=np.int32) + i for i in range(n_req)]
    want = [_dense_reference(p, max_new) for p in prompts]
    TRACER.clear()
    TRACER.enable()
    try:
        eng = _engine()
        landed = {}

        def mid():
            time.sleep(0.03)
            landed["ok"] = eng.hot_swap(PARAMS)      # identity weights

        got, dropped = _serve(eng, prompts, max_new, mid=mid)
        events = TRACER.snapshot()
    finally:
        TRACER.disable()

    trace = to_chrome(events)
    errors = validate(trace)
    round_trip = json.loads(json.dumps(trace)) == trace
    reqs = derive_requests(events)
    complete = sum(1 for r in reqs.values()
                   if r["done_ts"] is not None and r["ttft_ns"] is not None)
    cats = sorted({e.cat for e in events})
    rec = {"requests": n_req, "dropped": dropped,
           "tokens_exact": got == want,
           "swap_landed": bool(landed.get("ok")),
           "events": len(events),
           "chrome_events": len(trace["traceEvents"]),
           "categories": cats,
           "validate_errors": errors[:5],
           "complete_lifecycles": complete,
           "json_round_trip": round_trip}
    check(dropped == 0 and got == want,
          "hot-swap-under-traffic run: 0 dropped, tokens exact")
    check(landed.get("ok", False), "mid-serve hot-swap landed")
    check(not errors, f"chrome trace validates (errors: {errors[:3]})")
    check(round_trip, "chrome trace survives a json round-trip")
    check(complete == n_req,
          f"every request derives a complete lifecycle with TTFT "
          f"({complete}/{n_req})")
    check({"req", "lock", "engine"} <= set(cats),
          f"req+lock+engine categories all present (got {cats})")
    return rec


def bench_zero_sync(batch: int = 16) -> dict:
    TRACER.clear()
    TRACER.enable()
    try:
        reg = REG.BravoRegistry()
        h = reg.alloc("obs-xfer")
        rids = jnp.arange(batch, dtype=jnp.int32)
        g = h.acquire(rids)
        h.release(rids, granted=g)                  # warmup / compile
        guard_ok = True
        try:
            with jax.transfer_guard("disallow"):
                g = h.acquire(rids)
                h.release(rids, granted=g)
        except Exception as e:                      # pragma: no cover
            guard_ok = False
            print(f"  transfer_guard tripped: {e}", flush=True)
        st = reg.stats()                            # harvest AFTER the guard
    finally:
        TRACER.disable()
    check(guard_ok, "traced registry pair runs under "
                    "jax.transfer_guard('disallow')")
    return {"traced_guard_disallow_ok": guard_ok,
            "publishes": st["publishes"]}


def main() -> int:
    smoke = ARGS.smoke
    max_new = ARGS.tokens if not smoke else 4
    n_req = 3 if smoke else 6
    emit_rec = bench_emit_cost(n=50_000 if smoke else 200_000)
    rec = {
        "bench": "obs",
        "mode": "smoke" if smoke else "full",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "model": CFG.name,
        "emit_cost": emit_rec,
        "tick_cost": bench_tick_cost(n=5_000 if smoke else 20_000),
        "step_overhead": bench_step_overhead(
            max_new, n_req, emit_rec["enabled_emit_ns"]),
        "chrome_hotswap": bench_chrome_hotswap(max_new, n_req),
        "zero_sync": bench_zero_sync(),
        "failures": FAILURES,
    }
    out = ARGS.out
    if out is None and not smoke:
        out = str(Path(__file__).resolve().parents[1] / "BENCH_obs.json")
    if out:
        Path(out).write_text(json.dumps(rec, indent=1))
        print(f"wrote {out}", flush=True)
    print(json.dumps({k: rec[k] for k in ("emit_cost", "step_overhead")},
                     indent=1))
    if FAILURES:
        print(f"FAILED: {FAILURES}", file=sys.stderr)
        return 1
    print("obs bench OK", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
