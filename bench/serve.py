"""One run of a serving cell: set-up, the measured window, the drain, and
the check of what the window served.

The window drives ``ServingEngine`` in scheduler mode through its public
entry points only (``compile_steps``, ``start``, ``submit``, ``hot_swap``,
``request_compaction`` through the compactor thread, ``stop``); the
program's ``req`` / ``engine`` trace events give every request's
admission, first token and completion on the ``monotonic_ns`` clock.

Timeline of a run::

    set-up (weights, engine, compile, warm-up) | pre-roll | window | tail
                                               ^ schedule  ^ w0     ^ w1

The schedule starts ``preroll_s`` before the window so the window opens
on a loaded system; it keeps arriving after the window until every
request due in the window has finished (or ``drain_s`` has passed), so
the last ones are not served by an emptying system.
"""

from __future__ import annotations

import dataclasses
import gc
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench import loadgen, reference, weights
from bench.e2e import build_timeline
from bench.spec import Cell

TRACE_RING = 1 << 18        # events per thread: a window never wraps it
TRACE_S = 12.0              # device-trace length, centred in the window
WARM_RID = 1 << 30          # warm-up request ids, clear of the schedule's
WARM_THREADS = 8            # warm-up programs built at once
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
HBM_RESERVE = 1 << 30       # HBM the fit check leaves beside the store
SAMPLE_TOKENS = 1000        # served tokens the check compares, about
SAMPLE_MAX_REQUESTS = 16


@dataclasses.dataclass
class Run:
    """What a run leaves for the metric readers and the check."""
    cell: Cell
    seed: int
    timeline: object
    schedule: loadgen.Schedule
    served: Dict[int, np.ndarray]       # rid -> served tokens
    compiles: List[str]                  # programs compiled in the window
    dropped: int                         # trace events lost
    setup_s: float
    lateness_ms: List[float]             # submit - due, window requests
    memory_peak: int
    trace_ns: Optional[tuple]            # host clock around the trace
    chips: int
    params: object = None                # the weights, for the reference


def model_config(m: Dict):
    """The program's ModelConfig for a configuration's ``model`` group."""
    from repro.models.common import ModelConfig
    return ModelConfig(
        name=m["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab=m["vocab"], act=m["act"], glu=m["glu"],
        norm_eps=m["norm_eps"], rope_theta=m["rope_theta"],
        tie_embeddings=m["tie_embeddings"],
        param_dtype=jnp.dtype(m["param_dtype"]))


def kv_bytes_per_token(m: Dict, tp: int) -> int:
    """Page-store bytes per token on one chip (bf16 K and V, every
    layer; KV heads split over ``tp`` where they divide)."""
    kvh = m["n_kv_heads"] // tp if m["n_kv_heads"] % tp == 0 \
        else m["n_kv_heads"]
    return 2 * m["n_layers"] * kvh * m["head_dim"] * 2


def pool_pages(devices, m: Dict, sched: Dict, reserve: int) -> int:
    """Pages the store gets: what HBM leaves on the fullest chip after the
    weights, less ``reserve`` (activations, logits, lease tables), halved:
    the compiled decode step holds a second, relaid-out copy of the whole
    store as temporaries (its compile on the chip fails otherwise)."""
    free = min(d.memory_stats()["bytes_limit"]
               - d.memory_stats()["bytes_in_use"] for d in devices)
    page = kv_bytes_per_token(m, len(devices)) * sched["page_size"]
    return int((free - reserve) // (2 * page))


class CompileCounter:
    """Programs JAX builds (compiled or loaded from the persistent cache)
    while ``armed``: a window must build none."""

    def __init__(self):
        self.armed = False
        self.names: List[str] = []
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == BUILD_EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


def page_counts(sched, sch: loadgen.Schedule) -> tuple:
    """-> (page counts the engine's page-table write can take, the most
    pages a ref release can take) for this schedule: a page of growth, or
    at admission the pages of a prompt (never fewer than the shortest
    prompt's, never more than the longest request holds, also after a
    preemption has folded its served tokens into its prompt)."""
    lo = sched.pages_for(min(len(d.prompt) for d in sch.requests) + 1)
    top = min(sched.lanes, sched.pages_for(
        max(len(d.prompt) + d.max_new for d in sch.requests)))
    return [1] + list(range(max(lo, 2), top + 1)), top


def warm_up(eng, sched, mesh, sch: loadgen.Schedule, log) -> None:
    """Build, before the window, the programs whose shapes depend on the
    traffic, ``WARM_THREADS`` at a time: the engine's eager page-table
    writes and ref releases, one program per page count the schedule can
    produce (:func:`page_counts`), the compactor's live-rid vectors
    (powers of two up to the slot count) and the swap's revocation.  The
    ref releases run the pool's own program on copies of its owner
    vector: through the engine each takes the pool's write lock, and the
    builds would queue behind it one at a time."""
    from repro.serving.kv_pool import _programs
    slots = sched.max_slots
    t0 = time.perf_counter()
    tbl = jax.device_put(np.full((slots, sched.lanes), -1, np.int32),
                         NamedSharding(mesh, P()))
    tbl = tbl.at[0, 0:1].set(jnp.asarray(np.arange(1), jnp.int32))
    owner = eng.pages.pool.owner
    writes, top = page_counts(sched, sch)

    def write(n):
        jax.block_until_ready(
            tbl.at[0, 0:n].set(jnp.asarray(np.arange(n), jnp.int32)))

    def release(n):
        jax.block_until_ready(_programs().release_refs(
            jnp.copy(owner), jnp.asarray(np.full((n,), -1, np.int32))))

    with ThreadPoolExecutor(WARM_THREADS) as ex:
        list(ex.map(write, writes))
        list(ex.map(release, range(1, top + 1)))
    n = 1
    while True:
        eng.pages.compact(live=list(range(n)))
        if n >= slots:
            break
        n *= 2
    if not eng.hot_swap(new_params=eng.store.params):
        raise RuntimeError("the warm-up weight swap did not land")
    jax.block_until_ready(tbl)
    log(f"warm-up: {len(writes)} page-table writes, {top} ref releases, "
        f"compaction, swap in {time.perf_counter() - t0:.2f}s")


def warm_requests(eng, vocab: int, page_size: int, seed: int,
                  system_prompts: List[np.ndarray], log) -> None:
    """Serve, before the schedule, a prompt and then a second one that
    extends it past a page boundary: admission, chunked prefill, decode,
    the lease programs, prefix publish and hit, copy-on-write and
    reclaim all build their programs here, not in the window.  Then each
    of the mix's system prompts once, so the prefix cache holds them as
    a server that has been running holds them."""
    from repro.serving.engine import Request
    rng = np.random.default_rng(int(seed) + 7)
    a = rng.integers(1, vocab, 3 * page_size + 5).astype(np.int32)
    b = np.concatenate([a, rng.integers(1, vocab, page_size)
                        .astype(np.int32)])
    t0 = time.perf_counter()
    rid = WARM_RID
    for batch in ([a], [b], system_prompts):
        reqs = [Request(rid=rid + i, prompt=p, max_new=4)
                for i, p in enumerate(batch)]
        rid += len(batch)
        for r in reqs:
            eng.submit(r)
        for r in reqs:
            while not r.done.wait(0.05):
                eng.check_health()
                if time.perf_counter() - t0 > 300:
                    raise TimeoutError("the warm-up requests did not "
                                       "finish")
    log(f"warm-up requests served in {time.perf_counter() - t0:.2f}s")


def _profile(start_ns: int, stop_ns: int, out_dir: str, box: list) -> None:
    """Device trace over [start_ns, stop_ns) of the host clock."""
    time.sleep(max(0.0, (start_ns - time.monotonic_ns()) / 1e9))
    t0 = time.monotonic_ns()
    jax.profiler.start_trace(out_dir)
    t1 = time.monotonic_ns()
    time.sleep(max(0.0, (stop_ns - t1) / 1e9))
    t2 = time.monotonic_ns()
    jax.profiler.stop_trace()
    box.append(((t0 + t1) // 2, t2))


def serve(cell: Cell, seed: int, seconds: float, devices, *,
          trace_dir: Optional[str], t_process0: int, log: Callable,
          session_rate: Optional[float] = None) -> Run:
    from repro import configs
    from repro.dist.sharding import param_specs
    from repro.models import model as M
    from repro.obs import TRACER
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.scheduler import SchedulerConfig

    c = cell.config
    m, sc_d = c["model"], c["scheduler"]
    cfg = model_config(m)
    _, rules, _ = configs.get(c["program_arch"])
    chips = len(devices)
    mesh = Mesh(np.array(devices).reshape(1, chips), ("data", "model"))

    t0 = time.perf_counter()
    params = weights.make(
        m, (lambda s: param_specs(s, rules, mesh, decode=True), mesh), seed)
    want = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(params))):
        raise RuntimeError("the benchmark's weight tree does not match the "
                           "program's parameter layout")
    log(f"weights: {sum(x.size for x in jax.tree.leaves(params))} "
        f"parameters ({m['param_dtype']}) in {time.perf_counter() - t0:.2f}s")

    sched = SchedulerConfig(**sc_d)
    n_pages = int(c["pool_pages"])
    fit = pool_pages(devices, m, sc_d, HBM_RESERVE)
    log(f"pool: {n_pages} pages x {sc_d['page_size']} tokens "
        f"({kv_bytes_per_token(m, chips) * sc_d['page_size']} B/page/chip); "
        f"HBM fits {fit}")
    if fit < n_pages:
        raise RuntimeError(f"HBM fits {fit} pages, the configuration "
                           f"states {n_pages}")
    eng = ServingEngine(cfg, params, mesh=mesh, rules=rules,
                        n_pages=n_pages, scheduler=sched)
    for step, (secs, _) in eng.compile_steps().items():
        log(f"compile {step} step: {secs:.2f}s")
    mix = cell.traffic
    sch = loadgen.generate(mix, seed=seed, window_s=seconds,
                           vocab=m["vocab"], max_seq=sched.max_seq,
                           session_rate=session_rate)
    warm_up(eng, sched, mesh, sch, log)
    window = sch.in_window()
    log(f"schedule: {len(sch.requests)} requests, {len(window)} due in the "
        f"{seconds:.0f}s window, {sum(len(r.prompt) for r in window)} "
        f"prompt tokens, {sum(r.max_new for r in window)} max new")

    TRACER.capacity = TRACE_RING
    TRACER.clear()
    TRACER.enable()
    counter = CompileCounter()
    wr = mix.get("writers") or {}
    eng.start(swap_period_s=float(wr.get("swap_period_s", 0)),
              perturb=lambda p: p,
              compact_period_s=float(wr.get("compact_period_s", 0)))
    warm_requests(eng, m["vocab"], sched.page_size, seed,
                  sch.system_prompts, log)
    base = time.monotonic_ns() + int(0.05e9)
    w0 = base + int(sch.preroll_s * 1e9)
    w1 = w0 + int(seconds * 1e9)
    drain_end = w1 + int(float(mix["drain_s"]) * 1e9)
    prof_box: list = []
    prof = None
    if trace_dir:
        mid = (w0 + w1) // 2
        prof = threading.Thread(target=_profile, args=(
            mid - int(TRACE_S / 2 * 1e9), mid + int(TRACE_S / 2 * 1e9),
            trace_dir, prof_box), daemon=True)
        prof.start()

    reqs: Dict[int, Request] = {}
    late: List[float] = []
    in_win = {r.rid for r in window}
    armed = False
    try:
        def finished(now):        # every window request done, or too late
            return now >= drain_end or (now >= w1 and all(
                reqs[i].done.is_set() for i in in_win))

        for d in sch.requests:
            due = w0 + int(round(d.at_s * 1e9))
            while True:
                now = time.monotonic_ns()
                if not armed and now >= w0:
                    counter.armed = armed = True
                if now >= due or finished(now):
                    break
                time.sleep(min((due - now) / 1e9, 0.01))
            if finished(now):
                break
            r = Request(rid=d.rid, prompt=d.prompt, max_new=d.max_new)
            eng.submit(r)
            reqs[d.rid] = r
            if d.rid in in_win:
                late.append((time.monotonic_ns() - due) / 1e6)
            eng.check_health()
        while time.monotonic_ns() < w1:
            time.sleep(0.01)
        counter.armed = False
        for i in in_win:
            if i in reqs:
                reqs[i].done.wait(max(0.0,
                                      (drain_end - time.monotonic_ns()) / 1e9))
    finally:
        counter.armed = False
        eng.stop()
        if prof is not None:
            prof.join()
    TRACER.disable()
    events = TRACER.snapshot()
    dropped = TRACER.dropped()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    tl = build_timeline(events, sch.requests, w0, w1)
    served = {i: np.asarray(r.out) for i, r in reqs.items()
              if r.done.is_set() and r.out is not None}
    for rec in tl.every:
        if rec.rid not in served:        # the client never got its answer
            rec.done_ns = None
    run = Run(cell=cell, seed=seed, timeline=tl, schedule=sch, served=served,
              compiles=list(counter.names), dropped=dropped,
              setup_s=(w0 - t_process0) / 1e9, lateness_ms=late,
              memory_peak=peak, trace_ns=prof_box[0] if prof_box else None,
              chips=chips, params=params)
    del eng
    gc.collect()
    return run


class Sample(NamedTuple):
    rid: int
    prompt: np.ndarray
    out: np.ndarray          # the served tokens
    cached: int              # prompt tokens the prefix cache served
    due_ns: int


def sample_for_check(run: Run, target_tokens: int = SAMPLE_TOKENS,
                     max_requests: int = SAMPLE_MAX_REQUESTS) -> List:
    """Requests due in the window that were served in full, drawn from the
    seed, the longest answer first; then others until ``target_tokens``
    served tokens are in."""
    tl, sch = run.timeline, run.schedule
    by_rid = {d.rid: d for d in sch.requests}
    done = [r for r in tl.reqs if r.served and r.rid in run.served
            and len(run.served[r.rid]) == by_rid[r.rid].max_new]
    if not done:
        return []
    rng = np.random.default_rng(int(run.seed) + 1)
    longest = max(done, key=lambda r: (len(run.served[r.rid]),
                                       r.prompt_len))
    rest = [r for r in done if r is not longest]
    rng.shuffle(rest)
    pick, n = [longest], len(run.served[longest.rid])
    for r in rest:
        if n >= target_tokens or len(pick) >= max_requests:
            break
        pick.append(r)
        n += len(run.served[r.rid])
    return [Sample(r.rid, by_rid[r.rid].prompt, run.served[r.rid], r.cached,
                   r.due_ns) for r in pick]


def check(run: Run, log: Callable, control: bool = False) -> Dict:
    """The comparison that decides ``correct``, and the numbers beside
    their limits.  With ``control`` the float8 control stands in the
    program's place: the gap compared is that of the tokens it puts first
    at each served position (``bench/control.py``; never in a run)."""
    m = run.cell.config["model"]
    sample = sample_for_check(run)
    swaps = [s.t1 for s in run.timeline.spans.get("swap_land", [])]
    after_swap = sum(1 for s in sample
                     if any(t <= s.due_ns for t in swaps))
    t0 = time.perf_counter()
    res = reference.compare(run.params, m, sample, control=control,
                            log=log) if sample \
        else {"gap": float("inf"), "control_gap": float("inf"), "tokens": 0}
    log(f"reference: {len(sample)} requests, {res['tokens']} served tokens "
        f"({sum(1 for s in sample if s.cached)} with cached prefix pages, "
        f"{after_swap} due after a weight swap) in "
        f"{time.perf_counter() - t0:.1f}s")
    failed = len(run.timeline.failed())
    gap = res["control_gap"] if control else res["gap"]
    limits = [
        ("widest_logit_gap", gap, float(run.cell.config["gap_limit"])),
        ("failed_requests", failed, 0),
        ("window_compiles", len(run.compiles), 0),
        ("trace_events_dropped", run.dropped, 0),
    ]
    return {"correct": all(v <= lim for _, v, lim in limits),
            "limits": {k: {"value": v, "limit": lim}
                       for k, v, lim in limits},
            "sample": len(sample), "program_gap": res["gap"]}


def lateness(run: Run) -> Dict:
    v = run.lateness_ms or [0.0]
    return {"p50_ms": statistics.median(v), "max_ms": max(v),
            "n": len(run.lateness_ms)}
