"""Serving engine: mechanisms (threads + locks) under a scheduler (policy).

This is where the paper's technique is a first-class feature of the
framework, and since PR 4 the control plane is split in two:

* **The engine owns the mechanisms**: worker threads, the BRAVO host locks,
  the device registry lease batches, the jitted prefill/decode programs,
  and the device-resident batch state (page-index matrix, cache lengths,
  current tokens).  Every step takes **read** permission on the model-epoch
  lock and the KV page-map stripes — an extremely read-dominated pattern.
  A weight-updater thread occasionally hot-swaps the model (write lock);
  a page-manager thread requests compaction (write lock on the page table).
* **The scheduler owns the policy** (``serving.scheduler``): admission
  control (slot cap + page watermark, the concurrency-restriction idea of
  arXiv:1905.10818), chunked prefill interleaved with decode, and
  preemption/eviction ordered by page pressure from the
  :class:`~repro.serving.kv_pool.KVPool`.  It holds no threads, no locks
  and no device state, so the policy is unit-testable as a state machine.

Lock implementation is selectable (``--lock ba | bravo-ba | pthread |
bravo-pthread | percpu | cohort-rw``): with BRAVO, worker threads publish
themselves in the shared visible-readers table and never touch the central
reader counter, which is exactly the paper's claim — and the engine's
metrics report both throughput and the per-lock BRAVO statistics so the
effect is observable.

With ``device_leases=True`` (default) the epoch reads are additionally
routed through the *device*-side batched lease API: the engine builds ONE
``core.registry.BravoRegistry`` — one shared visible-readers table for the
whole address space, the paper's economy — and every guarded resource is a
registry lock with its own bias lane: the model-epoch lock, and the KV
pool's striped page locks.  Each step publishes the whole batch's request
ids in one fused, donation-aliased program (zero host sync), and the
weight updater / page compactor revoke ONLY their own lock's bias before
mutating — a weight swap never flaps the KV stripes' fast path (nor vice
versa).

Paged decode data flow (scheduler mode, ``scheduler=SchedulerConfig()``):
the KV page *contents* live in one device-resident page store
(``models.model.init_paged_caches``) owned by the engine; the (request ->
pages) *map* lives in the :class:`~repro.serving.kv_pool.KVPool`.  Each
tick the engine takes the page-map stripe leases and the model-epoch lease
for the WHOLE batch in one fused publish each, holds them across the step
— an allocate/reclaim on an involved stripe drains until the step's reads
are done — and the step reads pages directly through the gather-by-page
Pallas kernel (``kernels.paged_attn``).  Steady-state decode moves zero
bytes of lock or map traffic between host and device; only the generated
tokens come back.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.atomics import LiveMem
from ..core.device_bravo import LeaseHandle
from ..core.errors import DrainTimeout
from ..core.factory import LockEnv
from ..core.registry import BravoRegistry, RegistryHandle
from ..dist.sharding import page_store_specs
from ..models import model as M
from ..models.common import ModelConfig
from ..kernels.quant import quant_layout_tag
from ..obs import TRACER as _TR
from ..obs import clock_anchor
from ..obs.metrics import MetricsRegistry
from .kv_pool import KVPool, page_keys
from .scheduler import (LatencyFeedbackController, Phase, Scheduler,
                        SchedulerConfig, SlotState)
from .steps import (jit_step, make_decode_step, make_paged_prefill_step,
                    make_prefill_step)

# device lease handles share one protocol (acquire/release/revoke/rearm)
Lease = Optional[Union[LeaseHandle, RegistryHandle]]


@dataclasses.dataclass
class EngineConfig:
    """Engine *mechanism* timings (the scheduler config stays pure policy).

    Hoisted out of the thread loops so chaos tests can run at tight
    timings — and so the drain deadline the hot-swap writer hands the
    registry is a configuration, not a magic number buried in a poll."""
    handler_poll_s: float = 0.1     # legacy handlers' inq.get timeout
    idle_poll_s: float = 0.05       # scheduler loop's idle inq.get timeout
    join_timeout_s: float = 10.0    # stop()'s per-thread join bound
    drain_wait_poll_s: float = 0.0005  # lease revocation poll cadence
    drain_max_wait_s: float = 5.0   # bounded-drain deadline (DrainTimeout)
    swap_retries: int = 3           # hot_swap attempts after a DrainTimeout
    swap_backoff_s: float = 0.05    # base backoff between attempts (doubles)
    obs_warmup_steps: int = 2       # decode steps excluded from the step-
    #                                 latency histogram (compile outliers)


class EngineFailure(RuntimeError):
    """A worker thread died.  Carries every recorded failure as
    ``(thread_name, exception, scheduler_state)`` triples so the caller
    sees WHAT crashed and what the policy FSM looked like at that moment —
    the old ``t.join(timeout=...)`` swallowed all of it."""

    def __init__(self, failures):
        names = ", ".join(f"{n}: {type(e).__name__}({e})"
                          for n, e, _ in failures)
        super().__init__(f"{len(failures)} engine thread(s) died — {names}")
        self.failures = list(failures)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new: int = 16
    out: Optional[np.ndarray] = None
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    # SLO plane (PR 9): tenant/class label the request in SLOReport
    # folds; priority feeds the scheduler's per-class admission order
    tenant: str = ""
    cls: str = ""
    priority: int = 0
    # scheduler mode: keep the (vocab,) float32 logits the first generated
    # token was drawn from (one host copy per request, at its final
    # prefill chunk) — for checking a served run against a reference
    keep_first_logits: bool = False
    first_logits: Optional[np.ndarray] = None
    submit_ns: int = 0                 # monotonic_ns at submit(), TTFT's 0


_ENGINE_COUNTERS = (
    "decode_steps",
    "tokens_out",
    "prefills",
    "weight_swaps",
    "swap_retries",     # hot_swap attempts that hit a DrainTimeout
    "swap_failures",    # hot_swaps abandoned after all retries
    "compactions",
    "read_acquires",
    # prefix-cache accounting (scheduler mode)
    "pages_charged",    # pages actually allocated at admission
    "pages_saved",      # prompt pages served by shared reference
    "cow_copies",       # partial-page divergences copied on write
    "cached_tokens",    # prompt tokens whose prefill was skipped
)


class EngineStats:
    """Attribute view over the engine's ``engine.*`` metrics counters.

    PR 8 folded the old stats dataclass (and its dedicated mutex) into the
    metrics registry: writes go through :meth:`inc` — a lock-free
    per-thread cell add — and attribute reads (``stats.decode_steps``)
    aggregate the cells, keeping every existing call site working."""

    def __init__(self, metrics: MetricsRegistry):
        object.__setattr__(self, "_c", {
            n: metrics.counter(f"engine.{n}") for n in _ENGINE_COUNTERS})

    def inc(self, name: str, n: int = 1) -> None:
        self._c[name].add(n)

    def __getattr__(self, name: str) -> int:
        try:
            return self.__dict__["_c"][name].value
        except KeyError:
            raise AttributeError(name) from None

    def __setattr__(self, name, value):
        raise AttributeError(
            "EngineStats is a metrics view: use inc(name, n) to count")

    def asdict(self) -> Dict[str, int]:
        return {n: c.value for n, c in self._c.items()}


class ModelStore:
    """Epoch-versioned weights, guarded by a reader-writer lock (and,
    optionally, by a device-side lease handle mirroring the readers — a
    plain ``LeaseHandle`` or a registry lock; same protocol)."""

    def __init__(self, params, lock, leases: Lease = None):
        self.params = params
        self.epoch = 0
        self.lock = lock
        self.leases = leases

    def read(self):
        tok = self.lock.acquire_read()
        return tok, self.params, self.epoch

    def done_read(self, tok):
        self.lock.release_read(tok)

    def read_batch(self, reader_ids):
        """Epoch read for a whole request batch: the host read lock plus
        ONE fused device-lease publish for all ``reader_ids`` (device int32
        array) — no host-device synchronization on the fast path.  The
        returned token carries the grant mask so ``done_read_batch`` only
        clears the leases actually won (a denied reader must not wipe the
        slot of whoever it collided with)."""
        tok = self.lock.acquire_read()
        granted = gen = None
        if self.leases is not None:
            try:
                self.leases.rearm()      # host-clock check; dispatch only
                granted = self.leases.acquire(reader_ids)  # when inhibited
                gen = getattr(self.leases, "gen", None)
            except BaseException:        # never leak the host read lock
                self.lock.release_read(tok)
                raise
        return (tok, granted, gen), self.params, self.epoch

    def done_read_batch(self, tok, reader_ids):
        host_tok, granted, gen = tok
        try:
            if granted is not None:
                # generation check: if a stuck-lane scrub regenerated the
                # lock value since this acquire, our slots were already
                # scrubbed — a release through the REFRESHED handle would
                # hash to the new value's slots and could wipe a lease the
                # rearmed lock legitimately granted
                if gen is None or gen == getattr(self.leases, "gen", None):
                    self.leases.release(reader_ids, granted=granted)
        finally:
            self.lock.release_read(host_tok)

    def swap(self, new_params, **revoke_kw):
        """Install new weights: write lock, bounded drain of the device
        leases (``revoke_kw`` forwards ``max_wait_s``/``wait_poll_s``),
        then epoch bump.  A :class:`DrainTimeout` propagates BEFORE the
        params are touched — the caller degrades, readers keep decoding on
        the old epoch."""
        tok = self.lock.acquire_write()
        try:
            if self.leases is not None:
                self.leases.revoke(**revoke_kw)  # drain BRAVO-style
            self.params = new_params
            self.epoch += 1
        finally:
            self.lock.release_write(tok)


class PageTable:
    """Paged-KV bookkeeping (page -> request map), rwlock-guarded.

    Two backings share the API:

    * ``pool`` (the default in the engine): the map lives on DEVICE in a
      :class:`~repro.serving.kv_pool.KVPool` — allocate/reclaim/lookup are
      donated device programs and reads take registry stripe leases; the
      host rwlock stays as the thread-level write exclusion the pool
      requires of its callers.
    * host mode (``pool=None``): the legacy numpy owner array + Python
      free list, optionally mirrored by a single device lease handle."""

    def __init__(self, n_pages: int, lock, leases: Lease = None,
                 pool: Optional[KVPool] = None):
        self.lock = lock
        self.leases = leases
        self.pool = pool
        if pool is None:
            self.owner = np.full((n_pages,), -1, np.int64)
            self._free: List[int] = list(range(n_pages))

    @property
    def free(self) -> List[int]:
        """Free pages: the live Python free list (host mode) or a
        synchronized snapshot of the device pool (off the hot path)."""
        if self.pool is not None:
            return self.pool.free_pages()
        return self._free

    def lookup(self, rid: int) -> List[int]:
        tok = self.lock.acquire_read()
        ids = granted = None
        try:
            if self.pool is not None:
                return self.pool.lookup(rid)
            if self.leases is not None:
                # control plane: rid arrives as a host int, so this read
                # pays one tiny H2D upload (the decode fast path amortizes
                # its reader-id upload per batch instead — see run())
                self.leases.rearm()
                ids = jnp.asarray([rid], jnp.int32)
                granted = self.leases.acquire(ids)
            return list(np.where(self.owner == rid)[0])
        finally:
            # only clear what acquire granted; if acquire itself raised
            # (granted is None) an unmasked release could wipe a slot some
            # OTHER reader legitimately holds
            if granted is not None:
                self.leases.release(ids, granted=granted)
            self.lock.release_read(tok)

    def read_batch(self, rids: jax.Array):
        """Per-decode-step page-map read for a device-resident rid batch:
        one fused stripe-lease publish + ownership mask, zero host sync.
        Returns ``(token, mask)`` (mask None in host mode); the host read
        lock AND the stripe leases are held until ``done_read_batch`` —
        an allocate/reclaim on an involved stripe drains until then."""
        tok = self.lock.acquire_read()
        if self.pool is None:
            return (tok, None), None
        try:
            ptok, mask = self.pool.read_batch(rids)
        except BaseException:          # never leak the host read lock
            self.lock.release_read(tok)
            raise
        return (tok, ptok), mask

    def done_read_batch(self, token) -> None:
        host_tok, ptok = token
        try:
            if ptok is not None:
                self.pool.done_read_batch(ptok)
        finally:
            self.lock.release_read(host_tok)

    def allocate(self, rid: int, n: int) -> List[int]:
        """Pool mode dispatches the donated alloc program under the write
        lock but MATERIALIZES the page indices only after releasing it:
        the host-device sync is off the critical section, so the writer
        hold time (= the BRAVO revocation window every reader on this lock
        pays for) is bounded by dispatch cost, not a device round-trip."""
        tok = self.lock.acquire_write()
        try:
            if self.pool is not None:
                take, ok = self.pool.allocate_async(rid, n)
            else:
                if self.leases is not None:
                    self.leases.revoke()
                if len(self._free) < n:
                    return []
                pages = [self._free.pop() for _ in range(n)]
                self.owner[pages] = rid
                return pages
        finally:
            self.lock.release_write(tok)
        return self.pool.materialize_alloc(take, ok)   # sync OUTSIDE

    def reclaim(self, rid: int) -> int:
        tok = self.lock.acquire_write()
        try:
            if self.pool is not None:
                cnt = self.pool.reclaim_async(rid)
            else:
                if self.leases is not None:
                    self.leases.revoke()
                pages = list(np.where(self.owner == rid)[0])
                self.owner[pages] = -1
                self._free.extend(pages)
                return len(pages)
        finally:
            self.lock.release_write(tok)
        return int(cnt)                                # sync OUTSIDE

    # ---------------------------------------------------- prefix cache (PR 5)
    # All four run in pool mode only (the scheduler's data plane).  The
    # refcount mutators take the host WRITE lock for thread exclusion but
    # dispatch-only under it (materialize after release, like allocate) —
    # and none of them revokes a stripe bias: refcounts never change a
    # live rid's page mask or any page a leased reader can address, so a
    # prefix hit costs no reader its fast path.

    def match_prefix(self, kh, kl, ln):
        """Peek the prefix index (read lock; no refs taken)."""
        tok = self.lock.acquire_read()
        try:
            return self.pool.match_prefix(kh, kl, ln)
        finally:
            self.lock.release_read(tok)

    def acquire_prefix(self, kh, kl, ln, take):
        """Take refs on the hit run's ``take``-selected pages; -> (per-key
        page list, free pages consumed)."""
        tok = self.lock.acquire_write()
        try:
            res = self.pool.acquire_prefix_async(kh, kl, ln, take)
        finally:
            self.lock.release_write(tok)
        return self.pool.materialize_prefix(*res)      # sync OUTSIDE

    def insert_prefix(self, rid: int, kh, kl, ln, lane_pages) -> List[bool]:
        """Publish a request's written prompt pages; -> converted mask."""
        tok = self.lock.acquire_write()
        try:
            ins = self.pool.insert_prefix_async(rid, kh, kl, ln, lane_pages)
        finally:
            self.lock.release_write(tok)
        return np.asarray(ins).tolist()                # sync OUTSIDE

    def release_refs(self, pages) -> int:
        """Drop refs on shared pages; -> pages freed (refcount hit 0)."""
        tok = self.lock.acquire_write()
        try:
            cnt = self.pool.release_refs_async(pages)
        finally:
            self.lock.release_write(tok)
        return int(cnt)                                # sync OUTSIDE

    def compact(self, live=None) -> int:
        """Background compaction tick.

        Pool mode: scrub orphan pages — pages whose owner rid is not in
        ``live`` (e.g. leaked by a request torn down mid-flight).  The
        synchronizing part (the orphan PLAN) runs before the write lock is
        taken, and a clean plan never takes the lock at all; under the
        lock only the donated owner-vector swap (plus the flagged
        stripes' bias revocation) is dispatched, and the freed count is
        read back after release.  Holding the write lock across a device
        sync — the bug this replaces — stretched every reader's BRAVO
        revocation window by a full host round-trip.

        Host mode keeps its free list sorted (pure host work, no sync to
        hoist).  Returns the number of pages scrubbed."""
        if self.pool is not None:
            if live is None:
                return 0
            pad = 1
            while pad < max(len(live), 1):
                pad *= 2                       # bounded set of jit shapes
            live_arr = np.full((pad,), -1, np.int64)
            live_arr[:len(live)] = list(live)
            live_dev = jnp.asarray(live_arr, jnp.int32)
            per_stripe, total = self.pool.orphan_plan(live_dev)  # sync, no
            if total == 0:                                       # lock held
                return 0
            tok = self.lock.acquire_write()
            try:
                cnt = self.pool.scrub_orphans_async(live_dev,
                                                    per_stripe > 0)
            finally:
                self.lock.release_write(tok)
            return int(cnt)                    # sync OUTSIDE the lock
        tok = self.lock.acquire_write()
        try:
            self._free.sort()
        finally:
            self.lock.release_write(tok)
        return 0


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, mesh, rules,
                 lock_name: str = "bravo-ba", handlers: int = 4,
                 max_seq: int = 128, slots_per_handler: int = 4,
                 n_pages: int = 4096, env: Optional[LockEnv] = None,
                 device_leases: bool = True, kv_stripes: int = 4,
                 scheduler: Optional[SchedulerConfig] = None,
                 engine_cfg: Optional[EngineConfig] = None,
                 quant_kv: bool = False):
        self.cfg = cfg
        self.ecfg = engine_cfg or EngineConfig()
        self.mesh = mesh
        self.rules = rules
        self.env = env or LockEnv(LiveMem())
        # ONE metrics registry for the whole serving plane: the engine,
        # its lock registry and its KV pool share the namespace, so a
        # snapshot() is the full picture and tests never cross-contaminate
        self.metrics = MetricsRegistry()
        self.registry: Optional[BravoRegistry] = None
        self.kv_pool: Optional[KVPool] = None
        model_h = pool = None
        if device_leases:
            # ONE registry = one shared visible-readers table for every
            # device lock in the address space (the paper's economy); each
            # guarded resource gets its own bias lane, so a weight swap's
            # revocation never flaps the page locks' fast path
            self.registry = BravoRegistry(metrics=self.metrics)
            model_h = self.registry.alloc(name="model")
            self.kv_pool = pool = KVPool(n_pages, registry=self.registry,
                                         stripes=kv_stripes,
                                         metrics=self.metrics)
        self.store = ModelStore(params, self.env.make(lock_name),
                                leases=model_h)
        self.pages = PageTable(n_pages, self.env.make(lock_name), pool=pool)
        self.lock_name = lock_name
        self.handlers = handlers
        self.max_seq = max_seq
        self.slots = slots_per_handler
        self.stats = EngineStats(self.metrics)
        self._h_step = self.metrics.histogram("engine.step_ns")
        self._h_swap = self.metrics.histogram("engine.swap_ns")
        self._g_queue = self.metrics.gauge("engine.queue_depth")
        self.inq: "queue.Queue[Optional[Request]]" = queue.Queue()
        self._threads: List[threading.Thread] = []
        self._stop = threading.Event()
        # worker-thread failures: (name, exception, scheduler snapshot);
        # stop()/check_health() re-raise instead of swallowing
        self._failures: List[tuple] = []
        self._failures_lock = threading.Lock()
        self._degraded = threading.Event()   # hot-swap drain failed: stop
        #                                      admitting, drain in-flight
        self._prefill = jax.jit(make_prefill_step(cfg, mesh, rules))
        self._decode = jax.jit(make_decode_step(cfg, mesh, rules))

        # ---- scheduler mode (continuous batching over the paged pool) ----
        self.sched_cfg = scheduler
        self.scheduler: Optional[Scheduler] = None
        if scheduler is not None:
            if pool is None:
                raise ValueError("scheduler mode needs device_leases=True "
                                 "(the paged pool IS the data plane)")
            sc = scheduler
            self.scheduler = Scheduler(sc, n_pages)
            # the page STORE (contents); the pool above holds the MAP.
            # quant_kv=True stores pages int8 + per-(page, head) scales as
            # sibling leaves — every pool program below (scan, donation,
            # COW page copy) treats the store as an opaque pytree, so the
            # quantized layout rides through unchanged
            self.quant_kv = quant_kv

            def init_store():
                return M.init_paged_caches(cfg, n_pages, sc.page_size,
                                           quantized=quant_kv)
            # built in place on the mesh (KV heads split over "model" where
            # they divide): the store is the largest buffer the engine owns
            store_specs = page_store_specs(jax.eval_shape(init_store),
                                           cfg.n_kv_heads, mesh)
            self._pages_kv = jax.jit(init_store, out_shardings={
                k: NamedSharding(mesh, sp)
                for k, sp in store_specs.items()})()
            # quantized pages hash/dedup by their int8 bytes: the prefix
            # keys carry a layout tag so a quantized page key can never
            # alias a bf16 one (tag 0 keeps legacy chains bit-identical)
            self._quant_tag = (quant_layout_tag(sc.page_size,
                                                cfg.n_kv_heads, cfg.hd)
                               if quant_kv else 0)
            # pool HBM footprint: the whole point of the int8 store is the
            # byte bill, so it is a first-class gauge (+ Perfetto counter
            # track).  The store's shape is fixed for the engine's
            # lifetime, so one set at init is exact
            hbm = sum(int(x.nbytes) for x in jax.tree.leaves(self._pages_kv))
            self._g_hbm = self.metrics.gauge("pool.hbm_bytes")
            self._g_hbm.set(hbm)
            if _TR.enabled:
                _TR.emit("pool", "hbm_bytes", bytes=hbm,
                         quantized=int(quant_kv))
            # quant write/hit volume: O(1) increments from host-known tick
            # shapes, applied at tick top level AFTER the lease windows
            # close — never a device read inside a lease
            self._c_quant_tok = self.metrics.counter("pool.quant_tokens")
            self._c_quant_hit = self.metrics.counter("pool.quant_hits")
            ms, lanes = sc.max_slots, sc.lanes
            # device-resident step inputs, replicated over the mesh the
            # steps run on: touched only on control-plane events
            # (admission / growth / eviction); the decode tick reads it in
            # place with zero host traffic
            self._replicated = NamedSharding(mesh, P())
            self._page_tbl = self._put(np.full((ms, lanes), -1, np.int32))
            self._clen = self._put(np.zeros((ms,), np.int32))
            self._cur = self._put(np.zeros((ms, 1), np.int32))
            self._active = self._put(np.zeros((ms,), np.int32))
            # the batch's reader ids feed only the lease programs, which
            # live with the one lease table on the default device (a
            # Mosaic kernel cannot be partitioned over a mesh)
            self._rids = jnp.full((ms,), -1, jnp.int32)
            self._decode_paged = jit_step(
                make_decode_step(cfg, mesh, rules, paged=True),
                donate_argnums=(1,))
            self._prefill_paged = jit_step(
                make_paged_prefill_step(cfg, mesh, rules),
                donate_argnums=(1,))
            self._bump = jax.jit(lambda c, a: c + a)
            # copy-on-write: duplicate one page of the store (all layers,
            # K and V) into a private page before a divergent write
            self._copy_page = jit_step(
                lambda kv, src, dst: jax.tree.map(
                    lambda x: x.at[:, dst].set(x[:, src]), kv),
                donate_argnums=(0,))
            self._free_est = n_pages        # host mirror of pool pressure
            self._compact_req = False
            # scheduler loop iterations so far: every span the loop emits
            # carries the one that caused it as ``tick``
            self._tick = 0
            # decode steps seen so far: the first obs_warmup_steps stay
            # out of the latency histogram (compile-time outliers would
            # dominate p99 for the whole run)
            self._steps_seen = 0
            # ---- latency-feedback admission (PR 9): windowed sensors +
            # AIMD controller over the scheduler's runtime limits.  The
            # engine OBSERVES into the windows (O(1), next to the
            # existing histogram observes) and periodically lets the
            # controller read them — always at tick top level, never
            # inside a lease window
            self._controller = None
            self._w_step = self._w_ttft = None
            self._h_ttft = self.metrics.histogram("engine.ttft_ns")
            if sc.controller is not None:
                cc = sc.controller
                self._w_step = self.metrics.windowed(
                    "slo.step_ns", cc.window_s, cc.slices)
                self._w_ttft = self.metrics.windowed(
                    "slo.ttft_ns", cc.window_s, cc.slices)
                self._controller = LatencyFeedbackController(
                    cc, max_slots=sc.max_slots,
                    free_frac=sc.admit_free_frac,
                    step_window=self._w_step, ttft_window=self._w_ttft)
                self._g_slot_cap = self.metrics.gauge("sched.slot_cap")
                self._g_free_frac = self.metrics.gauge(
                    "sched.admit_free_frac")
                self._g_slot_cap.set(sc.max_slots)
                self._g_free_frac.set(sc.admit_free_frac)
                self._ctrl_next_ns = 0

    def _put(self, host_array: np.ndarray) -> jax.Array:
        """Upload step input, replicated over the engine's mesh."""
        return jax.device_put(host_array, self._replicated)

    # ------------------------------------------------------------- handlers
    def _handler(self, hid: int) -> None:
        B = self.slots
        cfg = self.cfg
        while not self._stop.is_set():
            # gather up to B requests
            reqs: List[Request] = []
            try:
                reqs.append(self.inq.get(timeout=self.ecfg.handler_poll_s))
            except queue.Empty:
                continue
            if reqs[0] is None:
                return
            while len(reqs) < B:
                try:
                    r = self.inq.get_nowait()
                    if r is None:
                        self.inq.put(None)
                        break
                    reqs.append(r)
                except queue.Empty:
                    break
            self._serve_batch(hid, reqs)

    def _serve_batch(self, hid: int, reqs: List[Request]) -> None:
        cfg = self.cfg
        B = len(reqs)
        S = max(len(r.prompt) for r in reqs)
        maxlen = self.max_seq
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt  # left-pad
            self.pages.allocate(r.rid, (len(r.prompt) + r.max_new + 63) // 64)
        # the batch's reader ids, device-resident once per batch: every
        # subsequent lease publish/clear is a single fused device program
        rid_dev = jnp.asarray([r.rid for r in reqs], jnp.int32)

        # prefill under a read lock (one epoch for the whole batch)
        tok, params, epoch = self.store.read_batch(rid_dev)
        try:
            last_logits, _ = self._prefill(params, {"tokens": jnp.asarray(toks)})
        finally:
            self.store.done_read_batch(tok, rid_dev)
        self.stats.inc("prefills")

        caches = M.init_caches(cfg, B, maxlen, dtype=jnp.bfloat16)
        # re-run prompt through decode steps to fill caches (simple engine;
        # per-slot lens differ so we feed token-by-token)
        outs = [[] for _ in range(B)]
        cur = jnp.asarray(toks[:, :1])
        max_new = max(r.max_new for r in reqs)
        for step in range(S - 1 + max_new):
            clen = jnp.full((B,), step + 1, jnp.int32)
            # page-map read held across the step: the stripe leases (and
            # host read lock) pin the batch's pages until the decode
            # dispatch is in — a compactor on those stripes drains first
            ptok, _page_mask = self.pages.read_batch(rid_dev)
            try:
                rtok, params_now, _ = self.store.read_batch(rid_dev)
                try:
                    nxt, logits, caches = self._decode(params_now, caches,
                                                       cur, clen)
                finally:
                    self.store.done_read_batch(rtok, rid_dev)
            finally:
                self.pages.done_read_batch(ptok)
            self.stats.inc("decode_steps")
            self.stats.inc("read_acquires")
            if step + 1 < S:
                cur = jnp.asarray(toks[:, step + 1:step + 2])
            else:
                cur = nxt
                nn = np.asarray(nxt)[:, 0]
                for i in range(B):
                    if len(outs[i]) < reqs[i].max_new:
                        outs[i].append(int(nn[i]))
        for i, r in enumerate(reqs):
            r.out = np.asarray(outs[i], np.int32)
            self.pages.reclaim(r.rid)
            r.done.set()
        self.stats.inc("tokens_out", sum(len(o) for o in outs))

    # ----------------------------------------------- scheduler mode (PR 4)
    def _submit_slot(self, r: Request) -> None:
        self.scheduler.submit(SlotState(
            rid=r.rid, prefix=np.asarray(r.prompt, np.int32),
            max_new=r.max_new, request=r, tenant=r.tenant, cls=r.cls,
            priority=r.priority, submit_ns=r.submit_ns))

    def _drain_inq(self) -> None:
        while True:
            try:
                r = self.inq.get_nowait()
            except queue.Empty:
                return
            if r is not None:        # None = legacy stop sentinel; the
                self._submit_slot(r)  # loop exits via _stop instead

    def _bind_pages(self, st: SlotState, pages: List[int],
                    charged: Optional[int] = None) -> None:
        """Append pages to the slot's lanes.  ``charged`` is how many FREE
        pages this binding consumed — shared-by-ref pages cost nothing
        unless the ref revived a refcount-0 cached page."""
        base = len(st.pages)
        st.pages.extend(pages)
        self._free_est -= len(pages) if charged is None else charged
        self._page_tbl = self._page_tbl.at[
            st.row, base:base + len(pages)].set(
                jnp.asarray(pages, jnp.int32))   # one dispatch, static slice

    def _clear_row(self, row: int) -> None:
        self._page_tbl = self._page_tbl.at[row].set(-1)
        self._clen = self._clen.at[row].set(0)
        self._cur = self._cur.at[row].set(0)
        self._rids = self._rids.at[row].set(-1)
        self._active = self._active.at[row].set(0)

    def _release_slot_pages(self, st: SlotState) -> int:
        """Return a slot's pages to the pool: drop its refs on shared
        prefix pages (a page is freed only at refcount 0 — a surviving
        sharer's pages are never touched), then reclaim its privates."""
        freed = 0
        if st.shared_refs:
            freed += self.pages.release_refs(
                np.asarray(st.shared_refs, np.int32))
            st.shared_refs = []
        return freed + self.pages.reclaim(st.rid)

    def _evict(self, st: SlotState) -> None:
        """Preempt under page pressure: drop refs + reclaim, requeue (the
        scheduler folds generated tokens into the prefix), clear the
        row."""
        row = st.row
        self._free_est += self._release_slot_pages(st)
        self.scheduler.evict(st)
        self._clear_row(row)
        if _TR.enabled:
            _TR.emit("req", "evict", rid=st.rid)

    def _finish(self, st: SlotState) -> None:
        row = st.row
        self._free_est += self._release_slot_pages(st)
        self.scheduler.finish(st)
        self._clear_row(row)
        if _TR.enabled:
            _TR.emit("req", "done", rid=st.rid, tokens=len(st.out))
        r = st.request
        if r is not None:
            r.out = np.asarray(st.out, np.int32)
            r.done.set()

    def _grow_slot(self, st: SlotState, n: int) -> bool:
        """Allocate ``n`` pages for a running slot, evicting newest-first
        (page-pressure preemption) until the allocation fits."""
        while True:
            pages = self.pages.allocate(st.rid, n)
            if pages:
                self._bind_pages(st, pages)
                return True
            victim = self.scheduler.pick_victim(exclude=st)
            if victim is None:
                return False
            self._evict(victim)

    def _peek_need(self, st: SlotState) -> int:
        """Post-dedup page charge for admission: a request pays only for
        the pages its prompt does NOT share with the prefix cache (plus
        any refcount-0 cached pages a hit would pin — those come off the
        free list too).  Also records the slot's cache plan: how many
        prompt tokens are covered, how many pages ride by reference, and
        whether the boundary page needs a copy-on-write."""
        sc = self.sched_cfg
        total = sc.pages_for(st.n_prefix + 1)
        if not sc.prefix_cache:
            return total
        pool = self.kv_pool
        if st.cache_plan is not None and st.cache_plan[0] == pool.version:
            return st.cache_plan[4]   # pool unchanged since the last peek:
        #                               no device round-trip per tick while
        #                               the slot waits at the watermark
        if st.keys is None:
            st.keys = page_keys(st.prefix, sc.page_size, pad_to=sc.lanes,
                                quant_tag=self._quant_tag)
        _, n_run, free_hit = self.pages.match_prefix(*st.keys)
        lens = st.keys[2]
        # usable coverage: the hit run's tokens, capped so the LAST prompt
        # token is always recomputed — its logits seed the first generated
        # token, and the scheduler's contract is exactness, not trust
        cov = min(int(np.sum(lens[:n_run])), st.n_prefix - 1)
        k_ref = cov // sc.page_size
        cow = cov % sc.page_size > 0
        # charge only the keys the attach will actually pin: refcount-0
        # hits consume a free page when revived, hits with live holders
        # are free of charge
        revived = sum(free_hit[:k_ref + (1 if cow else 0)])
        need = total - k_ref + revived
        st.cache_plan = (pool.version, cov, k_ref, cow, need)
        return need

    def _attach_prefix(self, st: SlotState) -> bool:
        """Bind an admitted slot's pages, deduplicated against the prefix
        cache: shared full pages ride by reference (refcount++), a
        partial-page divergence is COPIED into a private page (never
        written through — the cache holder may still be appending to it),
        and only the remainder is freshly allocated.  False -> the pool
        was short after all; the caller defers the slot."""
        sc = self.sched_cfg
        total = sc.pages_for(st.n_prefix + 1)
        cov, k_ref, cow = (st.cache_plan[1:4] if st.cache_plan
                           else (0, 0, False))
        refs: List[int] = []
        cow_src = -1
        revived = 0
        if k_ref or cow:
            take = np.zeros((sc.lanes,), bool)
            take[:k_ref + (1 if cow else 0)] = True
            hit, revived = self.pages.acquire_prefix(*st.keys, take)
            refs = [p for p in hit[:k_ref] if p >= 0]
            cow_src = hit[k_ref] if cow else -1
            if len(refs) != k_ref or (cow and cow_src < 0):
                # the cache changed between peek and acquire (possible only
                # if a caller bypasses the scheduler thread): drop whatever
                # was granted and fall back to a plain allocation.  NO
                # _free_est credit here — the revives were never debited
                # (only _bind_pages debits), so crediting the release
                # would inflate the estimate on every retry
                got = refs + ([cow_src] if cow_src >= 0 else [])
                if got:
                    self.pages.release_refs(np.asarray(got, np.int32))
                refs, cov, k_ref, cow, cow_src, revived = \
                    [], 0, 0, False, -1, 0
        pages = self.pages.allocate(st.rid, total - k_ref)
        if not pages:
            if refs or cow_src >= 0:
                # same rollback rule: the acquire was never debited
                got = refs + ([cow_src] if cow_src >= 0 else [])
                self.pages.release_refs(np.asarray(got, np.int32))
            st.cache_plan = None
            return False
        if cow:
            # lane k_ref: private copy of the divergent boundary page; the
            # transient ref pinned the source across the copy
            self._pages_kv = self._copy_page(
                self._pages_kv, jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(pages[0], jnp.int32))
            self._free_est += self.pages.release_refs(
                np.asarray([cow_src], np.int32))
        st.shared_refs = refs
        st.cached_pos = cov
        st.prefill_pos = st.pos = cov     # chunked prefill resumes here
        self._rids = self._rids.at[st.row].set(st.rid)
        self._bind_pages(st, refs + pages, charged=len(pages) + revived)
        self.stats.inc("pages_charged", len(pages))
        self.stats.inc("pages_saved", k_ref)
        self.stats.inc("cow_copies", int(cow))
        self.stats.inc("cached_tokens", cov)
        if self.quant_kv and cov:
            self._c_quant_hit.add(cov)   # tokens ridden as shared int8
        if _TR.enabled:
            _TR.emit("req", "admit", rid=st.rid, cached=cov,
                     pages=len(pages), shared=k_ref)
            if cow:
                _TR.emit("pool", "cow_copy", rid=st.rid)
        return True

    def _admit(self) -> None:
        """Admission: the scheduler applies the watermarks (charging each
        request its post-dedup page need); the engine attaches the
        admitted slots' pages — shared, copied or fresh (no eviction on
        admission: a new request never preempts running work)."""
        if self._degraded.is_set():
            return      # drain failure in flight: finish what's running on
            #             the old epoch, admit nothing new until the swap
            #             lands or is abandoned (concurrency restriction,
            #             arXiv:1905.10818 taken to its zero-admission end)
        admitted = self.scheduler.admit(self._free_est,
                                        need_fn=self._peek_need)
        for i, st in enumerate(admitted):
            if not self._attach_prefix(st):
                # the host free estimate was stale: un-admit this slot AND
                # every later one (reversed, so the queue keeps its order)
                # — a slot left running without pages would prefill into
                # nothing and stream garbage
                for back in reversed(admitted[i:]):
                    self.scheduler.defer(back)
                break

    def _publish_prefix(self, st: SlotState) -> None:
        """A slot just finished paging its prompt: offer its pages to the
        prefix index.  Only pages the slot OWNS convert (its shared-ref
        lanes are already published; the copy-on-write lane re-publishes
        only if the original entry was evicted meanwhile); converted pages
        move from the slot's private set to its ref list, so teardown
        releases them instead of reclaiming."""
        sc = self.sched_cfg
        kh, kl, ln = st.keys
        n_keys = int(np.sum(ln > 0))
        lane_pg = np.full((sc.lanes,), -1, np.int32)
        for i in range(n_keys):        # key i's page is lane i (the tail
            lane_pg[i] = st.pages[i]   # key covers lane n_prefix // ps)
        ins = self.pages.insert_prefix(st.rid, kh, kl, ln, lane_pg)
        st.shared_refs = st.shared_refs + [
            int(lane_pg[i]) for i in range(n_keys) if ins[i]]

    def _run_prefill(self, plan) -> None:
        """One chunked-prefill tick: right-aligned chunks for up to
        ``prefill_rows`` slots, under the page-stripe + model-epoch lease
        batch (held across the step, like decode)."""
        sc = self.sched_cfg
        rows, width, lanes = sc.prefill_rows, sc.prefill_chunk, sc.lanes
        toks = np.zeros((rows, width), np.int32)
        clens = np.zeros((rows,), np.int32)
        newls = np.zeros((rows,), np.int32)
        ptbl = np.full((rows, lanes), -1, np.int32)
        rids = np.full((rows,), -1, np.int32)
        for i, (st, chunk) in enumerate(zip(plan.slots, plan.chunks)):
            seg = st.prefix[st.prefill_pos:st.prefill_pos + chunk]
            toks[i, width - chunk:] = seg
            newls[i] = chunk
            clens[i] = st.prefill_pos + chunk
            ptbl[i, :len(st.pages)] = st.pages
            rids[i] = st.rid
        rid_dev = jnp.asarray(rids)
        args = map(self._put, (toks, clens, newls, ptbl))
        t0 = time.monotonic_ns()
        ptok, _ = self.pages.read_batch(rid_dev)
        try:
            rtok, params, _ = self.store.read_batch(rid_dev)
            try:
                t1 = time.monotonic_ns()
                nxt, last_logits, self._pages_kv = self._prefill_paged(
                    params, self._pages_kv, *args)   # uploads ``args``
                t2 = time.monotonic_ns()
            finally:
                self.store.done_read_batch(rtok, rid_dev)
        finally:
            self.pages.done_read_batch(ptok)
        t3 = time.monotonic_ns()
        nxt_h = np.asarray(nxt)
        t4 = time.monotonic_ns()
        if _TR.enabled:
            tick = self._tick
            _TR.emit_span("engine", "prefill_step", t0, dur_ns=t4 - t0,
                          rows=len(plan.slots), tick=tick)
            self._emit_step_phases(t0, t1, t2, t3, t4)
            for st, chunk in zip(plan.slots, plan.chunks):
                _TR.emit("req", "prefill_chunk", rid=st.rid, chunk=chunk,
                         pos=st.prefill_pos, tick=tick)
        done: List[SlotState] = []
        first_toks = 0
        for i, (st, chunk) in enumerate(zip(plan.slots, plan.chunks)):
            if self.scheduler.on_prefill(st, chunk):
                if sc.prefix_cache:
                    self._publish_prefix(st)   # prompt pages fully written
                tok = int(nxt_h[i])     # final chunk: first generated token
                first_toks += 1
                r = st.request
                if r is not None and r.keep_first_logits \
                        and r.first_logits is None:
                    r.first_logits = np.asarray(last_logits[i], np.float32)
                row = st.row
                self._cur = self._cur.at[row, 0].set(tok)
                self._clen = self._clen.at[row].set(st.pos + 1)
                self._active = self._active.at[row].set(1)
                if st.submit_ns:
                    # once, at the first token: a re-prefill after an
                    # eviction makes no new first token
                    ttft = time.monotonic_ns() - st.submit_ns
                    st.submit_ns = 0
                    self._h_ttft.observe(ttft)
                    if self._w_ttft is not None:
                        self._w_ttft.observe(ttft)
                if _TR.enabled:
                    _TR.emit("req", "first_token", rid=st.rid)
                if self.scheduler.on_token(st, tok):
                    done.append(st)     # max_new == 1
        for st in done:
            self._finish(st)
        self.stats.inc("prefills")
        self.stats.inc("read_acquires")
        self.stats.inc("tokens_out", first_toks)
        if self.quant_kv:
            self._c_quant_tok.add(int(np.sum(newls)))
        if _TR.enabled:
            _TR.emit_span("engine", "tick_finish", t4, tick=self._tick)

    def _run_decode(self, plan) -> None:
        """One decode tick over every DECODE row: grow pages first (with
        page-pressure eviction), then ONE fused lease batch per lock held
        across the step, one jitted step, zero host traffic on the lease
        fast path (only the generated tokens come back)."""
        tg = time.monotonic_ns()
        for st in plan.grow:
            if st.phase is not Phase.DECODE:
                continue                 # evicted by an earlier growth
            if not self._grow_slot(st, 1):
                self._evict(st)          # no other victim: requeue itself
        slots = [st for st in plan.slots if st.phase is Phase.DECODE]
        t0 = time.monotonic_ns()
        if _TR.enabled:
            _TR.emit_span("engine", "tick_grow", tg, dur_ns=t0 - tg,
                          tick=self._tick)
        if not slots:
            return
        rid_dev = self._rids
        ptok, _ = self.pages.read_batch(rid_dev)
        try:
            rtok, params, _ = self.store.read_batch(rid_dev)
            try:
                t1 = time.monotonic_ns()
                nxt, _logits, self._pages_kv = self._decode_paged(
                    params, self._pages_kv, self._cur, self._clen,
                    self._page_tbl)
                self._clen = self._bump(self._clen, self._active)
                t2 = time.monotonic_ns()
            finally:
                self.store.done_read_batch(rtok, rid_dev)
        finally:
            self.pages.done_read_batch(ptok)
        t3 = time.monotonic_ns()
        self._cur = nxt
        toks = np.asarray(nxt)[:, 0]     # the data-plane output sync
        t4 = time.monotonic_ns()
        dt = t4 - t0
        self._steps_seen += 1
        if self._steps_seen > self.ecfg.obs_warmup_steps:
            self._h_step.observe(dt)
            if self._w_step is not None:
                self._w_step.observe(dt)
        if _TR.enabled:
            # the decode kernel's grid, and the lanes of it that fetch and
            # compute: each row walks its ceil(len / page_size) live lanes
            sc = self.sched_cfg
            ps = sc.page_size
            _TR.emit_span("engine", "decode_step", t0, dur_ns=dt,
                          batch=len(slots), tick=self._tick,
                          lanes_live=sum(-(-st.pos // ps) for st in slots),
                          lanes_grid=sc.max_slots * sc.lanes)
            self._emit_step_phases(t0, t1, t2, t3, t4)
        done = [st for st in slots
                if self.scheduler.on_token(st, int(toks[st.row]))]
        for st in done:
            self._finish(st)
        self.stats.inc("decode_steps")
        self.stats.inc("read_acquires")
        self.stats.inc("tokens_out", len(slots))
        if self.quant_kv:
            self._c_quant_tok.add(len(slots))
        if _TR.enabled:
            _TR.emit_span("engine", "tick_finish", t4, tick=self._tick)

    def _emit_step_phases(self, t0: int, t1: int, t2: int, t3: int,
                          t4: int) -> None:
        """The four phases of a step span [t0, t4): both lease publishes
        (host read locks included), the step program's dispatch, both
        lease releases, and the wait for the tokens."""
        for name, a, b in (("lease_acquire", t0, t1),
                           ("step_dispatch", t1, t2),
                           ("lease_release", t2, t3),
                           ("step_readback", t3, t4)):
            _TR.emit_span("engine", name, a, dur_ns=b - a, tick=self._tick)

    def _ctrl_tick(self) -> None:
        """Latency-feedback admission update (paced to the controller's
        period).  Reads the windowed sensors — an aggregating read, legal
        here at tick top level, never inside a lease window — and applies
        any decision through ``scheduler.set_limits`` (the engine never
        assigns scheduler attributes; the lint enforces it)."""
        now = time.monotonic_ns()
        if now < self._ctrl_next_ns:
            return
        ctrl = self._controller
        self._ctrl_next_ns = now + int(ctrl.ccfg.period_s * 1e9)
        decision = ctrl.update(now)
        if decision is not None:
            self.scheduler.set_limits(ctrl.slot_cap, ctrl.free_frac)
            self._g_slot_cap.set(ctrl.slot_cap)
            self._g_free_frac.set(ctrl.free_frac)
            if _TR.enabled:
                _TR.emit("sched", f"ctrl_{decision}", cap=ctrl.slot_cap,
                         watermark_pct=round(ctrl.free_frac * 100, 1),
                         p99_step_us=round(ctrl.last_step_p99_ns / 1e3, 1),
                         p99_ttft_us=round(ctrl.last_ttft_p99_ns / 1e3, 1))
        if _TR.enabled:
            # periodic counter-track sample (Perfetto `C` events): the
            # watermark/slot curves line up with the latency they track
            _TR.emit("sched", "ctrl_state",
                     watermark_pct=round(ctrl.free_frac * 100, 1),
                     slot_cap=ctrl.slot_cap,
                     active_slots=len(self.scheduler.running),
                     p99_step_us=round(ctrl.last_step_p99_ns / 1e3, 1),
                     p99_ttft_us=round(ctrl.last_ttft_p99_ns / 1e3, 1))

    def _schedule_tick(self) -> bool:
        """One policy round: service compaction, admit, run the plan.
        Returns False when idle (the loop then blocks on the queue).  A
        step tick's phases are spans (``tick_compact``, ``tick_admit``,
        then those of the step it runs); an idle round emits none."""
        t0 = time.monotonic_ns()
        compacted = self._compact_req
        if compacted:
            self._compact_req = False
            live = [s.rid for s in self.scheduler.running.values()]
            self._free_est += self.pages.compact(live=live)
            self.stats.inc("compactions")
            if _TR.enabled:
                _TR.emit("engine", "compact")
        t1 = time.monotonic_ns()
        self._drain_inq()
        self._g_queue.set(len(self.scheduler.waiting))
        if self._controller is not None:
            self._ctrl_tick()
        self._admit()
        plan = self.scheduler.plan()
        if plan.kind not in ("prefill", "decode"):
            return False
        if _TR.enabled:
            clock_anchor()
            if compacted:
                _TR.emit_span("engine", "tick_compact", t0, dur_ns=t1 - t0,
                              tick=self._tick)
            _TR.emit_span("engine", "tick_admit", t1, tick=self._tick)
        if plan.kind == "prefill":
            self._run_prefill(plan)
        else:
            self._run_decode(plan)
        return True

    def _schedule_loop(self) -> None:
        """Run ticks until stopped.  Every iteration is one ``tick``; a
        step tick is one ``sched_tick`` span, and a run of idle
        iterations (each polls the queue for ``idle_poll_s``) one
        ``sched_idle`` span, emitted when the run ends."""
        idle_t0 = idle_tick = 0           # start of the current idle run
        while not self._stop.is_set():
            self._tick += 1
            t0 = time.monotonic_ns()
            if self._schedule_tick():
                if _TR.enabled:
                    if idle_t0:
                        _TR.emit_span("engine", "sched_idle", idle_t0,
                                      dur_ns=t0 - idle_t0, tick=idle_tick,
                                      polls=self._tick - idle_tick)
                    _TR.emit_span("engine", "sched_tick", t0,
                                  tick=self._tick)
                idle_t0 = 0
                continue
            if not idle_t0:
                idle_t0, idle_tick = t0, self._tick
                if _TR.enabled:
                    clock_anchor()
            try:
                r = self.inq.get(timeout=self.ecfg.idle_poll_s)
            except queue.Empty:
                continue
            if r is not None:
                self._submit_slot(r)
        if idle_t0 and _TR.enabled:
            _TR.emit_span("engine", "sched_idle", idle_t0, tick=idle_tick,
                          polls=self._tick - idle_tick + 1)

    def compile_steps(self) -> Dict[str, tuple]:
        """Compile the scheduler's paged decode and prefill steps ahead of
        time at the engine's fixed shapes, and serve with exactly these
        executables from then on (call before :meth:`start`; the first
        ticks then pay no compilation).  -> ``{step: (compile seconds,
        compiled executable)}`` — the executable's ``as_text()`` is the
        program every tick runs."""
        if self.scheduler is None:
            raise ValueError("compile_steps needs scheduler mode")
        sc = self.sched_cfg
        rows, width, lanes = sc.prefill_rows, sc.prefill_chunk, sc.lanes
        prefill_args = (np.zeros((rows, width), np.int32),
                        np.zeros((rows,), np.int32),
                        np.zeros((rows,), np.int32),
                        np.full((rows, lanes), -1, np.int32))
        out = {}
        for name, attr, args in (
                ("decode", "_decode_paged",
                 (self._cur, self._clen, self._page_tbl)),
                ("prefill", "_prefill_paged",
                 tuple(map(self._put, prefill_args)))):
            t0 = time.perf_counter()
            compiled = getattr(self, attr).lower(
                self.store.params, self._pages_kv, *args).compile()
            out[name] = (time.perf_counter() - t0, compiled)
            setattr(self, attr, compiled)
        return out

    # ------------------------------------------------------- background ops
    def _updater(self, period_s: float, perturb: Callable[[Any], Any]):
        while not self._stop.wait(period_s):
            self.hot_swap(perturb(self.store.params))

    def _compactor(self, period_s: float):
        while not self._stop.wait(period_s):
            self.request_compaction()

    def request_compaction(self) -> None:
        """One compaction tick: in scheduler mode the scheduler thread (the
        only page allocator there) services it at its next tick, so the
        live-rid snapshot can never race an in-flight admission; orphan
        pages it finds cost their stripes one revocation + drain."""
        if self.scheduler is not None:
            self._compact_req = True
        else:
            self.pages.compact()
            self.stats.inc("compactions")

    # ---------------------------------------------------- hot swap (PR 7)
    def stage_checkpoint(self, directory, step: int):
        """Stream a checkpoint into a SHADOW params pytree while serving
        continues.  Per-tensor checksums are verified leaf by leaf during
        the stream, so a corrupted shard raises
        :class:`~repro.ft.checkpoint.CheckpointCorrupt` here — before any
        lock is taken or epoch swapped.  No lock is held: staging runs
        entirely beside the decode fast path."""
        from ..ft.checkpoint import load_checkpoint
        if _TR.enabled:
            _TR.emit("engine", "swap_stage", step=step)
        return load_checkpoint(directory, step, like=self.store.params,
                               verify=True)

    def hot_swap(self, new_params: Any = None, *,
                 checkpoint: Optional[tuple] = None,
                 retries: Optional[int] = None,
                 backoff_s: Optional[float] = None) -> bool:
        """Weight hot-swap as a first-class serving operation.

        Stage (``checkpoint=(dir, step)`` streams + CRC-verifies into a
        shadow pytree; or pass ``new_params`` directly), then revoke the
        model-epoch leases with a BOUNDED drain and install.  On
        :class:`DrainTimeout` — a wedged reader, a dropped revocation ack —
        degrade instead of crashing: stop admitting (``_admit`` gates on
        the degraded flag), let in-flight decode finish on the OLD epoch,
        and retry with doubling backoff.  Returns True once the swap
        lands; False if all retries drained out — the engine resumes
        normal admission on the old weights, zero requests dropped."""
        if (new_params is None) == (checkpoint is None):
            raise ValueError(
                "hot_swap: pass exactly one of new_params / checkpoint")
        if checkpoint is not None:
            new_params = self.stage_checkpoint(*checkpoint)
        ecfg = self.ecfg
        retries = ecfg.swap_retries if retries is None else retries
        backoff = ecfg.swap_backoff_s if backoff_s is None else backoff_s
        for attempt in range(retries + 1):
            t0 = time.monotonic_ns()
            try:
                self.store.swap(new_params,
                                wait_poll_s=ecfg.drain_wait_poll_s,
                                max_wait_s=ecfg.drain_max_wait_s)
            except DrainTimeout:
                self.stats.inc("swap_retries")
                if attempt == retries:
                    self.stats.inc("swap_failures")
                    if _TR.enabled:
                        _TR.emit("engine", "swap_abandon", attempt=attempt)
                    self._degraded.clear()   # abandoned: keep serving the
                    return False             # old epoch, readmit traffic
                if _TR.enabled:
                    _TR.emit("engine", "swap_degrade", attempt=attempt)
                self._degraded.set()
                self._stop.wait(backoff * (2 ** attempt))
            else:
                self._degraded.clear()
                self.stats.inc("weight_swaps")
                self._h_swap.observe(time.monotonic_ns() - t0)
                if _TR.enabled:
                    _TR.emit_span("engine", "swap_land", t0,
                                  attempt=attempt,
                                  epoch=self.store.epoch)
                return True
        return False                         # unreachable; keeps mypy calm

    # --------------------------------------------------------------- public
    def _spawn(self, name: str, target: Callable, *args) -> None:
        """Start a worker whose death is RECORDED, not swallowed: the
        exception plus a scheduler-state snapshot land in ``_failures``
        and re-raise from ``stop()`` / ``check_health()``."""
        def body():
            try:
                target(*args)
            except BaseException as e:
                if _TR.enabled:
                    _TR.emit("engine", "worker_crash", thread=name,
                             error=type(e).__name__)
                snap = None
                try:
                    if self.scheduler is not None:
                        snap = self.scheduler.stats()
                except Exception:
                    pass                 # the snapshot must never mask e
                with self._failures_lock:
                    self._failures.append((name, e, snap))
        t = threading.Thread(target=body, name=name, daemon=True)
        t.start()
        self._threads.append(t)

    def start(self, *, swap_period_s: float = 0.0,
              perturb: Optional[Callable[[Any], Any]] = None,
              compact_period_s: float = 0.0) -> None:
        if self.scheduler is not None:
            self._spawn("scheduler", self._schedule_loop)
        else:
            for h in range(self.handlers):
                self._spawn(f"handler-{h}", self._handler, h)
        if swap_period_s > 0:
            pf = perturb or (lambda p: jax.tree.map(
                lambda x: x * (1.0 + 1e-6) if x.dtype.kind == "f" else x, p))
            self._spawn("updater", self._updater, swap_period_s, pf)
        if compact_period_s > 0:
            self._spawn("compactor", self._compactor, compact_period_s)

    def submit(self, req: Request) -> None:
        if self.sched_cfg is not None and \
                len(req.prompt) + req.max_new > self.sched_cfg.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + max_new "
                f"{req.max_new} exceeds scheduler max_seq "
                f"{self.sched_cfg.max_seq}")
        req.submit_ns = time.monotonic_ns()
        if _TR.enabled:
            _TR.emit("req", "submit", rid=req.rid,
                     prompt=len(req.prompt), max_new=req.max_new)
        self.inq.put(req)

    def check_health(self) -> None:
        """Raise :class:`EngineFailure` if any worker thread has died.
        Cheap (one lock, no dispatch) — callable from traffic loops."""
        with self._failures_lock:
            if self._failures:
                raise EngineFailure(self._failures)

    def stop(self) -> None:
        """Stop workers and RE-RAISE any recorded thread death — the old
        ``join(timeout=...)``-and-forget turned crashed schedulers into
        silently hung requests."""
        self._stop.set()
        for _ in self._threads:
            self.inq.put(None)
        for t in self._threads:
            t.join(timeout=self.ecfg.join_timeout_s)
        self.check_health()

    def lock_stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"engine": self.stats.asdict()}
        for name, lk in (("model", self.store.lock),
                         ("pages", self.pages.lock)):
            st = getattr(lk, "stats", None)
            if st is not None:
                out[name] = dataclasses.asdict(st)
        if self.registry is not None:
            out["device_leases"] = self.registry.stats()
            out["kv_pool"] = self.kv_pool.stats()
        if self.scheduler is not None:
            out["scheduler"] = self.scheduler.stats()
            if self._h_step.count:
                out["scheduler"]["decode_p50_us"] = round(
                    self._h_step.quantile(0.50) / 1e3, 2)
                out["scheduler"]["decode_p99_us"] = round(
                    self._h_step.quantile(0.99) / 1e3, 2)
        # the whole serving plane's metrics in one namespace (engine.*,
        # registry.*, pool.*) — the scattered per-subsystem stats dicts
        # above remain as compatibility views
        out["metrics"] = self.metrics.snapshot()
        return out
