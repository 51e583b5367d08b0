"""Multi-lock BRAVO registry: many locks, one visible-readers table.

The paper's central economy is that *all* reader-writer locks in an address
space share ONE visible-readers table while each lock adds only two small
private fields (RBias, InhibitUntil).  The first device port
(``core.device_bravo``) collapsed that to a single scalar ``rbias`` per
table — so one writer's revocation disabled the fast path for EVERY lock
multiplexed onto the table (the "shared-bias flap" in ROADMAP).

:class:`BravoRegistry` restores the paper's shape on device.  It multiplexes
up to ``MAX_LOCKS`` independent BRAVO locks over the one shared 16KB table
and keeps the per-lock private state as *vectors*:

``rbias`` — ``(MAX_LOCKS,) int32``, **device-resident**
    Read inside the fused publish kernel: each request gathers its own
    lock's bias lane (``kernels.ops.fused_publish_multi``), so a revocation
    of lock A undoes only A's publishes while B..Z keep landing in the same
    dispatch.  Mutated only by tiny donated scatter programs (arm / revoke).

``inhibit_until_ns`` / ``revoke_ewma_ns`` / ``revocations`` — host vectors
    Per-lock revocation bookkeeping for the adaptive
    N x revocation-cost rearm policy (:func:`~.bravo.adaptive_inhibit`,
    shared verbatim with the host BRAVO).  These live on the host because
    the policy is driven by the host monotonic clock; the device has no
    wall clock to compare against.

Lock-id allocation & recycling
------------------------------
``alloc()`` hands out a *bias lane index* from a free list plus a fresh
globally-unique lock **value** (``core.table.next_lock_id``) that readers
publish into table slots.  Recycling an index never resurrects stale
slots, twice over: ``free()`` scrubs every slot still publishing the old
value (one donated ``where(table == val, 0, table)`` program — defensive
against callers freeing with leases leaked), and the next allocation of
that index publishes a *different* value, so even a slot that somehow
survived cannot match the new lock's polls.

Concurrency contract
--------------------
Same as :class:`~.device_bravo.DeviceLeaseTable`: one host mutex guards the
host-side buffer swap; every operation is a single fused device dispatch.
Crucially the drain gate is per lock — ``_revoking[i]`` — so a writer
draining lock A never blocks ``rearm()`` of lock B (with the scalar table
that gate was necessarily global).  Compact NUMA-aware locks
(arXiv:1810.05600) motivates keeping the per-instance state this small;
Avoiding Scalability Collapse (arXiv:1905.10818) motivates arming each
lock's bias by its own measured revocation cost rather than a fixed
constant.

Writer parking & bounded drain (TWA-style)
------------------------------------------
Writers that must wait for ANOTHER writer's drain on the same lock used to
spin-poll the drain gate at a hardcoded 0.5 ms period (``free()``) or race
a second device poll loop against the first (``revoke()``).  Following the
waiting-array idea of *TWA — Ticket Locks Augmented with a Waiting Array*
(arXiv:1810.01573), the registry keeps a small shared array of parking
slots (``PARK_SLOTS`` condition variables) alongside the per-lock
drain-gate vector: a writer that finds ``_revoking[i]`` nonzero parks on
slot ``i % PARK_SLOTS`` and is woken when that lock's last in-flight drain
closes its gate.  Distinct locks may hash to the same slot — like TWA's
array, a wakeup is a *hint* (waiters recheck their own gate and re-park),
so collisions cost a spurious wake, never a lost one.

Every drain is deadline-bounded.  On deadline the writer raises the typed
:class:`~.errors.DrainTimeout` — after first running the **stuck-lane
scrub**: every table slot still publishing the lock's value is cleared and
the lane's lock value is REGENERATED (``next_lock_id``), exploiting the
same per-generation value discipline that makes lane recycling safe.  A
wedged reader's stale publish (or a delayed re-publish racing the scrub)
can therefore never match the lock once the caller rearms and retries;
release of a pre-scrub grant is skipped by generation check (the handle's
``gen`` bumps with the value).  The raise is deliberate: the wedged reader
may still be inside its critical section, so the WRITER must not proceed —
callers degrade (stop admitting, finish in-flight work, retry with
backoff; see ``ServingEngine.hot_swap``) instead of crashing.

``RegistryHandle`` implements the same protocol as ``LeaseHandle``
(``acquire`` / ``release`` / ``revoke`` / ``rearm`` + a ``lock_id``), so
``ModelStore`` / ``PageTable`` / ``make_distributed_revoke`` accept either.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import hash as H
from ..kernels import ops as K
from ..obs import TRACER as _TR
from ..obs.metrics import MetricsRegistry
from .bravo import DEFAULT_N, adaptive_inhibit
from .device_bravo import (TABLE_SLOTS, _drain, _lock_limbs,
                           _release_ids32_all_impl, _release_ids32_impl)
from .errors import DrainTimeout, ProtocolError
from .table import next_lock_id

__all__ = ["BravoRegistry", "RegistryHandle", "MAX_LOCKS", "PARK_SLOTS",
           "make_sharded_revoke"]

MAX_LOCKS = 128   # one VPU lane row of bias lanes per registry
PARK_SLOTS = 16   # TWA-style waiting array: parking slots shared by lanes


# ---------------------------------------------------------------------------
# Fused device programs (jitted once per shape; table/rbias donated)
# ---------------------------------------------------------------------------


def _acquire_impl(table, rbias_vec, reader_ids, lh, ll, lidx, val):
    """Publish leases for int32 ``reader_ids``; ``lh``/``ll``/``lidx``/
    ``val`` may be scalars (one lock) or (M,) vectors (requests spanning
    locks) — the hash and the one-hot bias gather broadcast either way."""
    tl = reader_ids.astype(jnp.uint32)
    th = jnp.zeros_like(tl)
    n_slots = table.shape[0] * table.shape[1]
    slots = H.hash_slots(lh, ll, th, tl, n_slots)
    lidx_v = jnp.zeros(tl.shape, jnp.int32) + lidx
    ids = jnp.zeros(tl.shape, jnp.int32) + val
    return K.fused_publish_multi(table, rbias_vec, slots, lidx_v, ids)


def _acquire_by_index_impl(table, rbias_vec, vals_vec, lock_idx, reader_ids):
    """Requests spanning locks addressed by bias-lane index alone: the lock
    values (and hence hash limbs) are gathered in-graph from the registry's
    device-resident ``vals_vec`` — nothing about the lock set crosses the
    host boundary per call."""
    val = vals_vec[lock_idx]
    ll = val.astype(jnp.uint32)
    lh = jnp.zeros_like(ll)     # lock ids are small ints: hi limb is 0
    return _acquire_impl(table, rbias_vec, reader_ids, lh, ll, lock_idx, val)


def _release_by_index_impl(table, vals_vec, lock_idx, reader_ids, granted):
    val = vals_vec[lock_idx]
    ll = val.astype(jnp.uint32)
    lh = jnp.zeros_like(ll)
    return _release_ids32_impl(table, reader_ids, lh, ll, granted)


def _scatter_impl(vec, idx, v):
    """One donated scatter serves both the rbias and lock-value vectors."""
    return vec.at[idx].set(v)


def _scrub_impl(table, val):
    """Clear every slot still publishing ``val`` (recycling hygiene)."""
    return jnp.where(table == val, 0, table)


class _Programs(NamedTuple):
    acquire: object
    acquire_by_index: object
    release: object
    release_all: object
    release_by_index: object
    scatter: object
    scrub: object


@functools.lru_cache(maxsize=None)
def _programs() -> _Programs:
    """jit the fused programs once, donating the mutated buffer (table or
    per-lock vector) via the shared :func:`~repro.kernels.ops.jit_donating`
    policy."""
    return _Programs(
        acquire=K.jit_donating(_acquire_impl, 1),
        acquire_by_index=K.jit_donating(_acquire_by_index_impl, 1),
        release=K.jit_donating(_release_ids32_impl, 1),
        release_all=K.jit_donating(_release_ids32_all_impl, 1),
        release_by_index=K.jit_donating(_release_by_index_impl, 1),
        scatter=K.jit_donating(_scatter_impl, 1),
        scrub=K.jit_donating(_scrub_impl, 1))


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------


class BravoRegistry:
    """Up to ``max_locks`` BRAVO locks multiplexed over one device table.

    Thread-safe like :class:`~.device_bravo.DeviceLeaseTable`: the mutex
    only guards the host-side buffer swap; each operation is one fused
    device dispatch.  All per-lock policy state is vectorized (see module
    docstring)."""

    def __init__(self, slots: int = TABLE_SLOTS,
                 max_locks: int = MAX_LOCKS, n: int = DEFAULT_N,
                 metrics: Optional[MetricsRegistry] = None):
        # the scan/poll kernels stream (BLOCK_ROWS, LANES) tiles
        if slots % (K.LANES * 8) != 0:
            raise ProtocolError(
                f"table slots {slots} must be a multiple of "
                f"{K.LANES * 8} (the scan/poll kernels stream "
                f"(BLOCK_ROWS, LANES) tiles)")
        self.max_locks = max_locks
        self.n = n
        self.table = jnp.zeros((slots // K.LANES, K.LANES), jnp.int32)
        self.rbias = jnp.zeros((max_locks,), jnp.int32)
        self.lock_vals = jnp.zeros((max_locks,), jnp.int32)  # device mirror
        self._mu = threading.Lock()
        # per-lock policy vectors (host clock drives the rearm policy)
        self.inhibit_until_ns = np.zeros(max_locks, np.int64)
        self.revoke_ewma_ns = np.zeros(max_locks, np.int64)
        self.revocations = np.zeros(max_locks, np.int64)
        self._armed = np.zeros(max_locks, bool)      # host shadow of rbias
        self._revoking = np.zeros(max_locks, np.int32)   # PER-LOCK drain gate
        self._vals = np.zeros(max_locks, np.int64)   # 0 = lane unallocated
        self._used = np.zeros(max_locks, bool)       # lane ever allocated
        self._free = list(range(max_locks - 1, -1, -1))
        # TWA-style waiting array: writers queueing behind an in-flight
        # drain park here (slot = lane % PARK_SLOTS) instead of spinning
        # on the gate; wakeups are hints, waiters recheck their own gate
        self._park = [threading.Condition(self._mu)
                      for _ in range(PARK_SLOTS)]
        # cached device scalars: rearm() is on the reader fast path and
        # must not upload anything (jax.transfer_guard-clean)
        self._one = jnp.ones((), jnp.int32)
        self._zero = jnp.zeros((), jnp.int32)
        # multi-pod mode (configure_mesh): revoke clears the bias lane on
        # its OWNING shard and polls with the hierarchical-psum count
        self._mesh = None
        self._sharded_revoke = None
        # observability: all counters live on the shared metrics registry
        # (engine passes its own so the whole serving plane snapshots as
        # one namespace); property accessors keep the old attribute API
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._c_publishes = self.metrics.counter("registry.publishes")
        self._c_allocs = self.metrics.counter("registry.allocs")
        self._c_recycles = self.metrics.counter("registry.recycles")
        # writers that parked on a busy drain
        self._c_parks = self.metrics.counter("registry.parks")
        # bounded drains that hit their deadline
        self._c_drain_timeouts = self.metrics.counter(
            "registry.drain_timeouts")
        # stuck-lane scrubs (value regenerated)
        self._c_lane_scrubs = self.metrics.counter("registry.lane_scrubs")
        self._h_revocation = self.metrics.histogram("registry.revocation_ns")
        self._h_drain_wait = self.metrics.histogram("registry.drain_wait_ns")

    # counter attribute compatibility (reads only; writes go through the
    # metrics registry so per-thread cells keep increments lock-free)
    @property
    def publishes(self) -> int:
        return self._c_publishes.value

    @property
    def allocs(self) -> int:
        return self._c_allocs.value

    @property
    def recycles(self) -> int:
        return self._c_recycles.value

    @property
    def parks(self) -> int:
        return self._c_parks.value

    @property
    def drain_timeouts(self) -> int:
        return self._c_drain_timeouts.value

    @property
    def lane_scrubs(self) -> int:
        return self._c_lane_scrubs.value

    def configure_mesh(self, mesh, axis=("pod", "data")) -> None:
        """Route revocation through :func:`make_sharded_revoke` — the
        ROADMAP follow-up for live multi-pod meshes.  The per-lock rbias
        vector is sharded WITH the table, so ``revoke`` clears only the
        lane on the shard that owns it (no MAX_LOCKS broadcast over the
        DCN), and the drain's match counts reduce hierarchically (psum the
        ICI axis first, one scalar per pod on the cross-pod fabric)
        instead of each poll scanning a replicated table.  Everything
        else — per-lock drain gates, the adaptive inhibit policy, the
        host shadow vectors — is unchanged.  Pass ``mesh=None`` to drop
        back to the host-path revoke."""
        with self._mu:
            if mesh is None:
                self._mesh = self._sharded_revoke = None
                return
            axes = (axis,) if isinstance(axis, str) else tuple(axis)
            lanes = 1
            for a in axes:
                lanes *= mesh.shape[a]
            if self.max_locks % lanes != 0:
                raise ProtocolError(
                    f"max_locks {self.max_locks} does not divide evenly "
                    f"over {lanes} mesh shards; each shard must own an "
                    f"equal run of bias lanes")
            self._mesh = mesh
            self._sharded_revoke = make_sharded_revoke(mesh, axes)

    # ------------------------------------------------------- lock lifecycle
    def alloc(self, name: Optional[str] = None) -> "RegistryHandle":
        """Allocate a lock: a free bias lane + a fresh lock value, armed."""
        with self._mu:
            if not self._free:
                raise ProtocolError(
                    f"registry full: all {self.max_locks} bias lanes are "
                    f"allocated (free() a handle before alloc())")
            idx = self._free.pop()
            val = next_lock_id()
            self._c_allocs.add(1)
            self._c_recycles.add(int(self._used[idx]))
            if _TR.enabled:
                _TR.emit("lock", "alloc", lane=idx, lock_id=val,
                         recycled=bool(self._used[idx]))
            self._used[idx] = True
            self._vals[idx] = val
            self._armed[idx] = True
            self._revoking[idx] = 0
            self.inhibit_until_ns[idx] = 0
            self.revoke_ewma_ns[idx] = 0
            self.revocations[idx] = 0
            i = jnp.asarray(idx, jnp.int32)
            self.rbias = _programs().scatter(self.rbias, i, self._one)
            self.lock_vals = _programs().scatter(self.lock_vals, i,
                                                 jnp.asarray(val, jnp.int32))
        return RegistryHandle(self, idx, val, name=name)

    # DeviceLeaseTable API parity: engine code can treat either as a factory
    handle = alloc

    def _park_until_idle(self, idx: int, deadline: float, who: str) -> None:
        """Park (TWA waiting array) until lane ``idx``'s drain gate closes.

        Caller holds ``self._mu`` (the conditions share it; ``wait``
        releases it while parked).  Wakeups are hints — a colliding lane's
        drain may notify this slot — so the gate is rechecked each wake.
        Raises :class:`DrainTimeout` at ``deadline``."""
        park = self._park[idx % PARK_SLOTS]
        t0 = None
        try:
            while self._revoking[idx]:
                if t0 is None:
                    t0 = time.monotonic_ns()
                    if _TR.enabled:
                        _TR.emit("lock", "park", lane=idx, who=who)
                self._c_parks.add(1)
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not park.wait(timeout=remaining):
                    if not self._revoking[idx]:
                        return        # gate closed exactly at the deadline
                    raise DrainTimeout(
                        f"{who}: revocation drain still in flight on lane "
                        f"{idx} (lock value {int(self._vals[idx])}) after "
                        f"parking past the deadline",
                        lock_id=int(self._vals[idx]), idx=idx)
        finally:
            if t0 is not None:
                self._h_drain_wait.observe(time.monotonic_ns() - t0)
                if _TR.enabled:
                    _TR.emit_span("lock", "unpark", t0, lane=idx, who=who)

    def _wake_parked(self, idx: int) -> None:
        """Notify lane ``idx``'s parking slot (caller holds ``self._mu``).
        notify_all, not notify: slot-sharing lanes' waiters must recheck."""
        self._park[idx % PARK_SLOTS].notify_all()

    def free(self, h: "RegistryHandle", wait_s: float = 5.0) -> None:
        """Recycle ``h``'s bias lane.  Does NOT wait for readers: any slot
        still publishing the old value is scrubbed in one donated program,
        and the next allocation of this lane publishes a different value —
        stale slots can never be resurrected.

        It DOES wait (up to ``wait_s``) for an in-flight ``revoke`` drain
        on this lock — parked on the waiting array, not spinning:
        recycling the lane mid-drain would let the drain's bookkeeping
        (the ``_revoking`` decrement, the inhibit stamp) land on the
        lane's NEXT tenant.  Raises :class:`DrainTimeout` at the cap."""
        deadline = time.monotonic() + wait_s
        with self._mu:
            if h.closed:
                return
            self._park_until_idle(h.idx, deadline, f"free({h.name})")
            h.closed = True
            idx = h.idx
            if _TR.enabled:
                _TR.emit("lock", "free", lane=idx, lock_id=h.lock_id)
            i = jnp.asarray(idx, jnp.int32)
            self.rbias = _programs().scatter(self.rbias, i, self._zero)
            self.lock_vals = _programs().scatter(self.lock_vals, i,
                                                 self._zero)
            self.table = _programs().scrub(
                self.table, jnp.asarray(h.lock_id, jnp.int32))
            self._vals[idx] = 0
            self._armed[idx] = False
            self._free.append(idx)

    @staticmethod
    def _check_open(h: "RegistryHandle") -> None:
        # a freed handle's lane may already belong to a NEW lock: an
        # acquire through it would be granted under the new tenant's bias
        # yet publish the DEAD lock value (undrainable by any live
        # revoke), and a release would blindly zero whatever slots it
        # hashes to — possibly a live lease of the lane's next tenant
        if h.closed:
            raise ProtocolError(
                f"{h.name}: handle used after free() (lane {h.idx}, dead "
                f"lock value {h.lock_id}); the lane may already belong to "
                f"a new lock")

    # -------------------------------------------------------------- readers
    def acquire(self, h: "RegistryHandle", reader_ids: jax.Array) -> jax.Array:
        """Publish leases for device-resident int32 ``reader_ids`` under
        ``h``'s lock; returns the granted mask without synchronizing."""
        with self._mu:
            self._check_open(h)
            self.table, granted = _programs().acquire(
                self.table, self.rbias, reader_ids, h._lh, h._ll,
                h._idx, h._val)
            self._c_publishes.add(1)
            if _TR.enabled:
                _TR.emit("lock", "publish", lock=h.name,
                         batch=int(reader_ids.size))
        return granted

    def release(self, h: "RegistryHandle", reader_ids: jax.Array,
                granted: Optional[jax.Array] = None) -> None:
        """Clear leases; pass acquire's ``granted`` mask so denied readers
        never clear the slot they collided into."""
        with self._mu:
            self._check_open(h)
            if granted is None:
                self.table = _programs().release_all(
                    self.table, reader_ids, h._lh, h._ll)
            else:
                self.table = _programs().release(
                    self.table, reader_ids, h._lh, h._ll, granted)

    def acquire_by_index(self, lock_idx: jax.Array,
                         reader_ids: jax.Array) -> jax.Array:
        """One fused dispatch for a request batch SPANNING locks: each
        request names its lock by bias-lane index (device int32).  Lock
        values/limbs are gathered in-graph from the device-resident
        mirror — zero host traffic about which locks are involved."""
        with self._mu:
            self.table, granted = _programs().acquire_by_index(
                self.table, self.rbias, self.lock_vals, lock_idx, reader_ids)
            self._c_publishes.add(1)
            if _TR.enabled:
                _TR.emit("lock", "publish", lock="by_index",
                         batch=int(reader_ids.size))
        return granted

    def release_by_index(self, lock_idx: jax.Array, reader_ids: jax.Array,
                         granted: jax.Array) -> None:
        with self._mu:
            self.table = _programs().release_by_index(
                self.table, self.lock_vals, lock_idx, reader_ids, granted)

    def lower_acquire_by_index(self, lock_idx: jax.Array,
                               reader_ids: jax.Array):
        """The fused lease-publish program :meth:`acquire_by_index`
        dispatches, lowered for these operands (``.compile().as_text()``
        shows what runs on the device); publishes nothing."""
        with self._mu:
            return _programs().acquire_by_index.lower(
                self.table, self.rbias, self.lock_vals, lock_idx, reader_ids)

    # ------------------------------------------------------------ the writer
    def revoke(self, h: "RegistryHandle", *, n: Optional[int] = None,
               wait_poll_s: float = 0.0005, max_wait_s: float = 5.0,
               pipeline_depth: int = 2) -> int:
        """Clear ``h``'s bias lane (only!), drain its leases, and set its
        per-lock inhibit deadline from its measured revocation cost.  Other
        locks' biases, drains and rearms are untouched throughout.

        With a mesh configured (:meth:`configure_mesh`) the lane clear and
        the drain polls both run through the sharded collective: the clear
        lands on the lane's owning shard, and each poll reduces
        hierarchically instead of scanning a replicated table."""
        n = self.n if n is None else n
        idx = h.idx
        sharded = self._sharded_revoke
        deadline = time.monotonic() + max_wait_s
        with self._mu:
            self._check_open(h)
            # a second writer (epoch swap racing pool compaction) parks on
            # the first writer's drain instead of polling the table
            self._park_until_idle(idx, deadline, f"revoke({h.name})")
            if sharded is not None:
                self.rbias, _ = sharded(self.table, self.rbias, h)
            else:
                self.rbias = _programs().scatter(self.rbias, h._idx,
                                                 self._zero)
            self._armed[idx] = False
            self._revoking[idx] += 1
            self.revocations[idx] += 1
            if _TR.enabled:
                _TR.emit("lock", "revoke_begin", lock=h.name, lane=idx)

        def poll_live(lid):
            # dispatch under the mutex: the scan is ordered on the current
            # table buffer BEFORE any later acquire/release donates it
            with self._mu:
                if sharded is not None:
                    # idempotent re-clear of an already-cleared lane; the
                    # hierarchical count is the poll result
                    self.rbias, cnt = sharded(self.table, self.rbias, h)
                    return cnt
                return K.revocation_poll(self.table, lid)

        try:
            start = time.monotonic_ns()
            try:
                scans = _drain(poll_live, h.lock_id,
                               wait_poll_s=wait_poll_s,
                               max_wait_s=max_wait_s,
                               pipeline_depth=pipeline_depth)
            except DrainTimeout as e:
                now = time.monotonic_ns()
                self._h_revocation.observe(now - start)
                if _TR.enabled:
                    _TR.emit("lock", "revoke_timeout", lock=h.name,
                             lane=idx, cost_ns=now - start)
                with self._mu:
                    self._c_drain_timeouts.add(1)
                    self._scrub_stuck_lane(h)
                    # a timed-out drain is still a (pathological) measured
                    # revocation cost: stamp the inhibit window so a
                    # degrade-and-retry loop backs off the rearm too
                    ewma, window = adaptive_inhibit(
                        int(self.revoke_ewma_ns[idx]), now - start, n)
                    self.revoke_ewma_ns[idx] = ewma
                    self.inhibit_until_ns[idx] = now + window
                e.idx = idx
                raise
            now = time.monotonic_ns()
            self._h_revocation.observe(now - start)
            if _TR.enabled:
                _TR.emit_span("lock", "revoke_drain", start, lock=h.name,
                              lane=idx, scans=scans)
            with self._mu:
                ewma, window = adaptive_inhibit(
                    int(self.revoke_ewma_ns[idx]), now - start, n)
                self.revoke_ewma_ns[idx] = ewma
                self.inhibit_until_ns[idx] = now + window
        finally:
            with self._mu:
                self._revoking[idx] -= 1
                if not self._revoking[idx]:
                    self._wake_parked(idx)
        return scans

    def _scrub_stuck_lane(self, h: "RegistryHandle") -> None:
        """Fence off a wedged reader after a drain deadline (mutex held).

        Scrubs every slot still publishing ``h``'s value and REGENERATES
        the lane's lock value — the per-generation discipline that makes
        lane recycling safe.  The wedged reader's stale publish can never
        match the rearmed lock, and its eventual release is gen-skipped by
        the owner (the handle's ``gen`` bumps with the value).  Does NOT
        clear the caller's raise: the reader may still be in its critical
        section, so revoke must still fail and the caller must degrade."""
        idx = h.idx
        self.table = _programs().scrub(
            self.table, jnp.asarray(h.lock_id, jnp.int32))
        new_val = next_lock_id()
        self._vals[idx] = new_val
        self.lock_vals = _programs().scatter(
            self.lock_vals, h._idx, jnp.asarray(new_val, jnp.int32))
        h.lock_id = new_val
        h._lh, h._ll = _lock_limbs(new_val)
        h._val = jnp.asarray(new_val, jnp.int32)
        h.gen += 1
        self._c_lane_scrubs.add(1)
        if _TR.enabled:
            _TR.emit("lock", "lane_scrub", lock=h.name, lane=idx)
            _TR.emit("lock", "gen_bump", lock=h.name, lane=idx, gen=h.gen)

    def rearm(self, h: "RegistryHandle") -> bool:
        """Re-arm ``h``'s bias iff ITS drain count is zero and ITS inhibit
        window has passed — a drain in flight on lock A never gates lock
        B's rearm (the multi-lock fix over the scalar table's global
        gate)."""
        idx = h.idx
        with self._mu:
            self._check_open(h)
            if self._armed[idx]:
                return True               # no dispatch on the hot path
            if self._revoking[idx]:
                return False              # never re-bias under OUR drain
            if time.monotonic_ns() >= int(self.inhibit_until_ns[idx]):
                self.rbias = _programs().scatter(self.rbias, h._idx,
                                                 self._one)
                self._armed[idx] = True
                if _TR.enabled:
                    _TR.emit("lock", "rearm", lock=h.name, lane=idx)
                return True
        return False

    # ---------------------------------------------------------------- stats
    def held(self, h: "RegistryHandle") -> int:
        """Hold count for one lock (synchronizing; off the hot path)."""
        with self._mu:
            return int(K.revocation_poll(self.table, h.lock_id))

    def held_multi(self, handles) -> np.ndarray:
        """Exact per-lock hold counts in ONE table pass (synchronizing)."""
        vals = jnp.asarray([h.lock_id for h in handles], jnp.int32)
        with self._mu:
            return np.asarray(K.revocation_poll_multi(self.table, vals))

    def stats(self) -> dict:
        """Synchronizing summary; call off the hot path."""
        with self._mu:
            live = int((self._vals != 0).sum())
            return {"max_locks": self.max_locks,
                    "live_locks": live,
                    "allocs": self.allocs,
                    "recycles": self.recycles,
                    "publishes": self.publishes,
                    "revocations": int(self.revocations.sum()),
                    "parks": self.parks,
                    "drain_timeouts": self.drain_timeouts,
                    "lane_scrubs": self.lane_scrubs,
                    "armed": int(self._armed.sum()),
                    "rbias_armed": int(jnp.sum(self.rbias))}


# ---------------------------------------------------------------------------
# Multi-pod revocation with the rbias vector sharded WITH the table
# ---------------------------------------------------------------------------


def make_sharded_revoke(mesh, axis=("pod", "data")):
    """Distributed revocation for REGISTRY locks: the per-lock ``rbias``
    vector is sharded over the same mesh axes as the table rows, so
    clearing one lock's bias touches only the shard that OWNS that lane —
    ``make_distributed_revoke`` on a registry handle otherwise replicates
    the full (MAX_LOCKS,) vector, i.e. every revocation broadcasts it over
    the slow DCN "pod" axis.  Match counts reduce hierarchically (psum the
    ICI axis first, DCN last — the RMA-locks pattern), one scalar per pod
    on the cross-pod fabric.

    ``axis`` is a mesh axis name or an outermost-first tuple.  Returns
    ``fn(table_sharded, rbias_sharded, lock) -> (rbias_sharded', count)``;
    ``lock`` is a :class:`RegistryHandle` (or any object with ``idx`` +
    ``lock_id``).  The lane product of the axes must divide ``MAX_LOCKS``
    for the rbias shard to be even (128 lanes / 32-way pod x data shard =
    4 lanes per shard on the 512-chip dry-run topology)."""
    from jax.sharding import PartitionSpec as P

    from ..dist.sharding import axis_size, hierarchical_psum, shard_map_compat

    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    missing = [a for a in axes if a not in mesh.axis_names]
    if missing:
        raise ProtocolError(
            f"mesh {mesh.axis_names} lacks axes {missing} required for "
            f"the sharded revoke")

    def body(table_shard, rbias_shard, lidx, lid):
        lanes = rbias_shard.shape[0]
        didx = jnp.zeros((), jnp.int32)
        for a in axes:                  # outermost-first flattened shard id
            didx = didx * axis_size(a) + jax.lax.axis_index(a)
        local = lidx - didx * lanes     # off-shard -> out of range -> no-op
        rb = jnp.where(jnp.arange(lanes) == local, 0, rbias_shard)
        cnt = jnp.sum((table_shard == lid).astype(jnp.int32))
        return rb, hierarchical_psum(cnt, axes)

    fn = jax.jit(shard_map_compat(
        body, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(), P()),
        out_specs=(P(axes), P()), check_vma=False))

    def rev(table_sharded, rbias_sharded, lock):
        return fn(table_sharded, rbias_sharded,
                  jnp.asarray(lock.idx, jnp.int32),
                  jnp.asarray(lock.lock_id, jnp.int32))

    return rev


class RegistryHandle:
    """One lock's view of a :class:`BravoRegistry`.

    Protocol-compatible with :class:`~.device_bravo.LeaseHandle` (acquire /
    release / revoke / rearm, plus ``lock_id``), so the serving engine's
    ``ModelStore``/``PageTable`` and ``make_distributed_revoke`` take
    either.  Caches the device-resident lock limbs / lane index so the
    steady state transfers nothing."""

    def __init__(self, registry: BravoRegistry, idx: int, lock_id: int,
                 name: Optional[str] = None):
        self.registry = registry
        self.idx = idx                 # bias lane in rbias[...]
        self.lock_id = lock_id         # value published into table slots
        self.name = name or f"reglock{idx}"
        self.closed = False
        self.gen = 0                   # bumps on stuck-lane value scrub
        self._lh, self._ll = _lock_limbs(lock_id)
        self._idx = jnp.asarray(idx, jnp.int32)
        self._val = jnp.asarray(lock_id, jnp.int32)

    def acquire(self, reader_ids: jax.Array) -> jax.Array:
        return self.registry.acquire(self, reader_ids)

    def release(self, reader_ids: jax.Array,
                granted: Optional[jax.Array] = None) -> None:
        self.registry.release(self, reader_ids, granted=granted)

    def revoke(self, **kw) -> int:
        return self.registry.revoke(self, **kw)

    def rearm(self) -> bool:
        return self.registry.rearm(self)

    def held(self) -> int:
        return self.registry.held(self)

    def free(self) -> None:
        self.registry.free(self)
