"""Device-resident paged-KV pool with registry reader locks and a
device-side prefix-cache page index.

ROADMAP named the serving engine's paged-KV cache as the last host-side
bookkeeping on the data plane: ``PageTable`` kept a numpy ``owner`` array
and a Python free list, so every allocate/reclaim/lookup round-tripped the
page map through the host.  :class:`KVPool` moves the map onto the device:

* ``owner`` is a device-resident ``(n_pages,) int32`` vector; allocation,
  reclamation and lookup are single donated jit programs (rank/cumsum-based
  first-fit, masked scatter, equality masks) — the page map never
  materializes on the host on the hot path.
* The per-page reader locks are **registry locks sharing the global
  visible-readers table**: pages are striped over ``stripes`` locks from a
  :class:`~repro.core.registry.BravoRegistry` (per-page locks at KV scale
  would exhaust bias lanes; striping keeps per-lock state tiny, exactly the
  compact-lock economy of arXiv:1810.05600).  Readers publish leases on
  their request's stripe; a writer (allocate/reclaim) revokes only that
  stripe's bias, so compaction on one stripe never flaps the bias of the
  other stripes — or of any other lock in the address space.
* The batch read fast path (:meth:`lookup_batch`) is ONE fused lease
  publish for a device-resident rid vector — stripe indices, lock values
  and hash limbs are all gathered in-graph (``acquire_by_index``), so a
  steady-state decode step moves zero bytes between host and device.

Prefix cache (PR 5): refcounts folded into the owner vector
-----------------------------------------------------------
Identical prompt prefixes used to burn fresh pages (and fresh publish
traffic) per request.  BRAVO's core move — diffuse cheap reader state over
one shared structure so the common case costs O(1) — extends to prompt
pages: share the page, count the readers, and reserve writer-side work
(copy-on-write) for the rare divergence.  Per the compact-footprint
discipline of arXiv:1810.05600 the refcounts live IN the owner vector, not
in a second table:

    ``owner[p] >= 0``   private page of request rid ``owner[p]``
    ``owner[p] == -1``  free (refcount 0) — and still CACHED if a prefix
                        entry points at it: free pages double as the cache,
                        so "evicting" cache is just allocating the page
    ``owner[p] <= -2``  shared, refcount ``-1 - owner[p]``

The prefix index is a set-associative device hash map (``map_slots``
power-of-two slots grouped into ``min(4, map_slots)``-way sets — PR 9
measured a 0.47 collision rate on the Zipf trace for the direct-mapped
original, i.e. nearly half of would-be hits silently missed): per slot
the full 64-bit chained splitmix64 key (two int32 limbs, hashed by
:func:`page_keys` via ``kernels.hash`` — the same finalizer the lease
table uses), the page it describes, the number of valid tokens in that
page (``page_size`` for full pages, less for the one partial-tail entry
a prompt may publish), and an insert-time age stamp.  A lookup probes
every way of its key's set; an insert takes the first vacant way or
evicts the OLDEST entry when the set is full (eviction drops only the
map entry — the victim page's owner/refcount state is untouched, so a
shared victim keeps serving its existing holders).  Lookup,
ref-acquisition, insert and ref-release are donated in-graph programs;
nothing about the cached prefix set crosses the host boundary except the
per-admission decision.

Invariants the programs maintain:

* a live map entry's page has not been reallocated since insert —
  allocation scrubs the entries of every page it takes (so a hit can trust
  the page CONTENT, not just the key);
* at most one live entry points at any page (entries are only created for
  pages freshly converted from the inserting request's private set);
* a shared page is freed only at refcount zero (:meth:`release_refs`), and
  the orphan scrub treats any ``refcount > 0`` page as live no matter
  which rids are — the "preempted sharer never frees the survivor's
  pages" contract;
* allocation prefers free pages with NO cache entry, so cached pages are
  evicted only under genuine page pressure (the admission watermark of
  arXiv:1905.10818 stays the only back-pressure mechanism).

Copy-on-write: a request whose prompt DIVERGES inside a cached page (or
must re-write its final token — the "first decode token recomputed
exactly" rule) never writes through the shared page.  The pool hands the
caller the hit so it can copy the page contents into a private page and
write there; the transient ref taken by :meth:`acquire_prefix` pins the
source until the copy lands (see ``ServingEngine._attach_prefix``).

The pool holds the page *map*; the page *contents* (the KV tensors) live
in the engine's page store (``models.model.init_paged_caches``) and are
read by page index through the ``kernels.paged_attn`` /
``kernels.paged_chunk_attn`` streaming kernels — neither decode nor
chunked prefill ever materializes a dense cache.

Writers must hold external write exclusion (the engine's host rwlock) —
the pool revokes/drains device leases, it does not arbitrate host threads.
Every writer splits into a dispatch half (``*_async``, safe under that
lock: it enqueues donated programs without synchronizing) and a
materialize half the caller runs AFTER dropping the lock, so the writer
hold time — the BRAVO revocation window — never includes a host-device
round-trip.  The refcount programs (acquire/insert/release) mutate only
page *lifetime* state, never any live request's (rid -> pages) mask or any
page a reader could currently address, so they skip the stripe-bias
revocation entirely: a prefix hit costs no reader anywhere its fast path.
"""

from __future__ import annotations

import functools
import threading
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import ProtocolError
from ..core.registry import BravoRegistry
from ..kernels.hash import _K1, _K2, _K3
from ..obs import TRACER as _TR
from ..obs.metrics import MetricsRegistry

__all__ = ["KVPool", "FREE", "page_keys", "PREFIX_SEED"]

FREE = -1

# chain seed for the prefix keys (any odd 64-bit constant; distinct from a
# token value so an empty chain never collides with a real one)
PREFIX_SEED = 0xB5297A4D3F84D5A9
_MASK64 = (1 << 64) - 1


def _mix(state: int, token: int) -> int:
    """``kernels.hash.mix_hash_u64`` on plain Python ints (bit-identical;
    the per-token chain runs on the engine's scheduler thread, so it must
    not pay a numpy round-trip per token)."""
    x = (state * _K1 + token * _K2) & _MASK64
    x ^= x >> 30
    x = (x * _K2) & _MASK64
    x ^= x >> 27
    x = (x * _K3) & _MASK64
    return x ^ (x >> 31)


def _refcount(owner):
    """Vectorized refcount view of the owner encoding (0 for private and
    free pages)."""
    return jnp.maximum(-1 - owner, 0)


def page_keys(tokens: np.ndarray, page_size: int, pad_to: int = 0,
              quant_tag: int = 0) -> Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]:
    """Chained splitmix64 prefix keys for a prompt.

    ``keys[i]`` hashes tokens ``[0, (i+1) * page_size)`` — the whole
    prefix, not just page ``i``'s tokens, because a page's KV content
    depends on everything before it.  A non-aligned prompt also emits one
    partial-tail key over the full prompt.  Returns int32 ``(hi, lo)``
    limb vectors plus per-key valid-token counts (``page_size`` for full
    pages, the tail remainder for the tail key, 0 for padding), padded to
    ``pad_to`` entries so the in-graph programs compile once per geometry.

    ``quant_tag`` (``kernels.quant.quant_layout_tag``) is mixed into the
    chain seed when nonzero: a quantized engine's keys describe int8
    bytes under a specific page geometry, so they must never alias an
    entry minted for a different byte layout.  Page bytes are a
    deterministic function of the token prefix GIVEN the layout (the
    quantizer is deterministic and a full page's requant round trip is
    bit-stable), so tagging the chain keeps dedup/COW bit-exact on the
    quantized bytes.  0 (the default, and the unquantized engines' value)
    leaves the legacy chain unchanged."""
    toks = [int(t) for t in np.asarray(tokens)]
    n = len(toks)
    state = _mix(PREFIX_SEED, quant_tag) if quant_tag else PREFIX_SEED
    keys: List[int] = []
    lens: List[int] = []
    for i, t in enumerate(toks):
        state = _mix(state, t)
        if (i + 1) % page_size == 0:
            keys.append(state)
            lens.append(page_size)
    if n % page_size:
        keys.append(state)
        lens.append(n % page_size)
    m = max(pad_to, len(keys))
    kh = np.zeros((m,), np.int32)
    kl = np.zeros((m,), np.int32)
    ln = np.zeros((m,), np.int32)
    for i, (k, l) in enumerate(zip(keys, lens)):
        kh[i] = np.int32(np.uint32(k >> 32))
        kl[i] = np.int32(np.uint32(k & 0xFFFFFFFF))
        ln[i] = l
    return kh, kl, ln


# ---------------------------------------------------------------------------
# Device programs (owner vector + map vectors donated where mutated)
# ---------------------------------------------------------------------------


def _alloc_impl(owner, map_pg, scale_gen, rid, n):
    """``n`` is a TRACED scalar: request sizes vary per prompt, and a
    static n would recompile this program for every distinct page count on
    the serving path.  The taken-pages result is a mask (static shape); the
    caller derives indices host-side — AFTER dropping any write lock it
    holds (see :meth:`KVPool.allocate_async`).

    Cache-aware first fit: free pages WITHOUT a prefix entry are taken
    first, cached-free pages only when the plain ones run out — and taking
    a cached page evicts its entry (the content is about to be
    overwritten), which keeps the hit-can-trust-content invariant.

    ``scale_gen`` is the per-page scale-metadata epoch (quantized pools):
    bumping it for every taken page marks any previously derived scale
    stale, so "a reallocated page always gets a fresh scale" is an
    observable transition, not just a write-path convention."""
    n_pages = owner.shape[0]
    free = owner == FREE
    cached = jnp.zeros((n_pages,), bool).at[
        jnp.where(map_pg >= 0, map_pg, n_pages)].set(True, mode="drop")
    plain = free & ~cached
    n_plain = jnp.sum(plain.astype(jnp.int32))
    rank = jnp.where(plain, jnp.cumsum(plain.astype(jnp.int32)),
                     n_plain + jnp.cumsum((free & cached).astype(jnp.int32)))
    enough = jnp.sum(free.astype(jnp.int32)) >= n
    take = free & (rank <= n) & enough
    new_owner = jnp.where(take, rid, owner)
    stale = (map_pg >= 0) & take[jnp.clip(map_pg, 0)]
    return (new_owner, jnp.where(stale, -1, map_pg),
            scale_gen + take.astype(jnp.int32), take, enough)


def _reclaim_impl(owner, rid):
    """Free ``rid``'s PRIVATE pages only — shared pages the request holds
    refs on are returned via :meth:`KVPool.release_refs` instead."""
    mine = owner == rid
    return jnp.where(mine, FREE, owner), jnp.sum(mine.astype(jnp.int32))


def _mask_impl(owner, rid):
    return owner == rid


def _mask_batch_impl(owner, rids):
    return owner[None, :] == rids[:, None]          # (B, n_pages)


def _free_count_impl(owner):
    return jnp.sum((owner == FREE).astype(jnp.int32))


def _stripe_lanes_impl(stripe_idx, rids, *, stripes: int):
    return stripe_idx[rids % stripes]


def _match_impl(owner, map_kh, map_kl, map_pg, map_ln, kh, kl, ln, *,
                ways: int):
    """Prefix lookup: per-key probe of every way in the key's set, reduced
    to the longest PREFIX run (a hole in the chain — some page evicted —
    invalidates everything after it: chunked prefill can only skip a
    contiguous prefix).  -> (per-key page or -1, run length, per-key
    currently-refcount-0 flags — acquiring such a hit consumes a free
    page, and the caller charges admission only for the keys it will
    actually take, and the lookup's COLLISION count: keys whose set is
    FULL of other keys' entries, i.e. set conflicts where this lookup
    could not even have hit — with a vacant way a no-match is a genuine
    miss, not a conflict)."""
    n_sets = map_pg.shape[0] // ways
    m = kh.shape[0]
    slots = (kl & (n_sets - 1))[:, None] * ways \
        + jnp.arange(ways)[None, :]                      # (m, ways)
    pg_w = map_pg[slots]
    occ = pg_w >= 0
    key_eq = (map_kh[slots] == kh[:, None]) \
        & (map_kl[slots] == kl[:, None]) & (map_ln[slots] == ln[:, None])
    hit_w = occ & key_eq & (ln[:, None] > 0)
    hit = jnp.any(hit_w, axis=1)
    pg = jnp.where(hit, pg_w[jnp.arange(m), jnp.argmax(hit_w, axis=1)], -1)
    run = jnp.cumprod(hit.astype(jnp.int32)) > 0
    pages = jnp.where(run, pg, -1)
    free_hit = run & (owner[jnp.clip(pg, 0)] == FREE)
    coll = (ln > 0) & ~hit & jnp.all(occ & ~key_eq, axis=1)
    return (pages, jnp.sum(run.astype(jnp.int32)), free_hit,
            jnp.sum(coll.astype(jnp.int32)))


def _acquire_prefix_impl(owner, map_kh, map_kl, map_pg, map_ln,
                         kh, kl, ln, take, *, ways: int):
    """Ref-acquisition half of a prefix hit: re-derive the hit run in the
    same program (so the refs land exactly on what was matched) and bump
    the refcount of every hit the caller's ``take`` mask selects.  Returns
    the taken pages (-1 elsewhere) and how many came off the free list."""
    n_pages = owner.shape[0]
    pages, _, _, _ = _match_impl(owner, map_kh, map_kl, map_pg, map_ln,
                                 kh, kl, ln, ways=ways)
    use = (pages >= 0) & take
    tgt = jnp.where(use, pages, n_pages)
    revived = jnp.sum((use & (owner[jnp.clip(pages, 0)] == FREE))
                      .astype(jnp.int32))
    new_owner = owner.at[tgt].add(-1, mode="drop")   # refcount++
    return new_owner, jnp.where(use, pages, -1), revived


def _insert_prefix_impl(owner, map_kh, map_kl, map_pg, map_ln, map_age,
                        kh, kl, ln, lane_pg, rid, stamp, *, ways: int):
    """Publish a request's freshly written prompt pages into the index:
    key ``i`` maps to the request's page ``lane_pg[i]``, which converts
    from private to shared-refcount-1 (the inserter's own ref — its reads
    must outlive any later hit).  Keys insert one after another, in lane
    order.  Way choice per key: a key already present in its set is
    skipped (the older entry keeps serving hits); otherwise the first
    VACANT way, or — set full — the way with the OLDEST ``map_age`` stamp
    among entries of EARLIER inserts is evicted (entry only; the victim
    page's owner/refcount state is untouched).  A key never evicts an
    entry of its own batch: that would punch a hole into the chain it is
    publishing, and a hole ends every later match.  A set full of this
    batch's entries skips the key.  ``stamp`` is the pool's monotonic
    insert clock (traced scalar)."""
    n_pages = owner.shape[0]
    map_slots = map_pg.shape[0]
    n_sets = map_slots // ways
    set_i = kl & (n_sets - 1)
    valid = (ln > 0) & (lane_pg >= 0) \
        & (owner[jnp.clip(lane_pg, 0)] == rid)

    def body(i, carry):
        own, mkh, mkl, mpg, mln, mage, ins = carry
        slots = set_i[i] * ways + jnp.arange(ways)
        occ = mpg[slots] >= 0
        present = jnp.any(occ & (mkh[slots] == kh[i])
                          & (mkl[slots] == kl[i]) & (mln[slots] == ln[i]))
        vac = ~occ
        older = occ & (mage[slots] < stamp)
        age_w = jnp.where(older, mage[slots], jnp.iinfo(jnp.int32).max)
        way = jnp.where(jnp.any(vac), jnp.argmax(vac), jnp.argmin(age_w))
        ok = valid[i] & ~present & (jnp.any(vac) | jnp.any(older))
        tgt = jnp.where(ok, slots[way], map_slots)
        pg = jnp.where(ok, lane_pg[i], n_pages)
        return (own.at[pg].set(-2, mode="drop"),      # refcount 1
                mkh.at[tgt].set(kh[i], mode="drop"),
                mkl.at[tgt].set(kl[i], mode="drop"),
                mpg.at[tgt].set(lane_pg[i], mode="drop"),
                mln.at[tgt].set(ln[i], mode="drop"),
                mage.at[tgt].set(stamp, mode="drop"),
                ins.at[i].set(ok))

    return jax.lax.fori_loop(
        0, kh.shape[0], body,
        (owner, map_kh, map_kl, map_pg, map_ln, map_age,
         jnp.zeros(kh.shape, bool)))


def _release_refs_impl(owner, pages):
    """Drop one ref per listed page (-1 entries ignored).  Guarded so a
    double release can never push a shared page past FREE into the private
    encoding; a page reaching refcount 0 becomes free — and stays CACHED
    (its map entry survives until allocation takes the page)."""
    n_pages = owner.shape[0]
    delta = jnp.zeros_like(owner).at[
        jnp.where(pages >= 0, pages, n_pages)].add(1, mode="drop")
    shared = owner <= -2
    new_owner = jnp.where(shared, jnp.minimum(owner + delta, FREE), owner)
    freed = jnp.sum((shared & (new_owner == FREE)).astype(jnp.int32))
    return new_owner, freed


def _orphan_plan_impl(owner, live, *, stripes: int):
    """Per-stripe orphan-page counts + total: pages whose owner rid is
    neither free, nor refcount-held (``owner <= -2`` — a shared page is
    live while ANY request holds a ref, whether or not its rids appear in
    ``live``), nor in ``live`` (a -1-padded vector of live rids)."""
    is_live = jnp.any(owner[:, None] == live[None, :], axis=1) \
        | (owner == FREE) | (_refcount(owner) > 0)
    orphan = ~is_live
    stripe_of = jnp.where(owner >= 0, owner % stripes, 0)
    per = jnp.sum(orphan[:, None]
                  & (stripe_of[:, None] == jnp.arange(stripes)[None, :]),
                  axis=0)
    return per, jnp.sum(orphan.astype(jnp.int32))


def _scrub_impl(owner, live):
    """Free every orphan page (recheck against ``live`` IN GRAPH, so a
    plan computed before the write lock was taken can never free a page
    that became live in between).  Refcount-aware: a ``refcount > 0`` page
    is live by definition — preempting one sharer must never free the
    surviving sharers' pages."""
    is_live = jnp.any(owner[:, None] == live[None, :], axis=1) \
        | (owner == FREE) | (_refcount(owner) > 0)
    return jnp.where(is_live, owner, FREE), jnp.sum(~is_live)


def _shared_stats_impl(owner, map_pg):
    return (jnp.sum((owner <= -2).astype(jnp.int32)),
            jnp.sum(_refcount(owner)),
            jnp.sum((map_pg >= 0).astype(jnp.int32)))


def _fold_hits_impl(acc, pages):
    """Fold a prefix acquisition's hit-page count into a device scalar:
    the per-tick dedup-hit counter stays device-resident (dispatch-only
    add) and is harvested only by the synchronizing ``stats()``."""
    return acc + jnp.sum((pages >= 0).astype(jnp.int32))


class _Programs(NamedTuple):
    alloc: object           # donates owner + map_pg + scale_gen
    reclaim: object
    mask: object
    mask_batch: object
    free_count: object
    stripe_lanes: object    # static stripes
    match: object           # static ways
    acquire_prefix: object  # donates owner; static ways
    insert_prefix: object   # donates owner + the five map vectors;
    #                         static ways
    release_refs: object    # donates owner
    orphan_plan: object     # static stripes
    scrub: object
    shared_stats: object
    fold_hits: object       # donates the accumulator scalar


@functools.lru_cache(maxsize=None)
def _programs() -> _Programs:
    from ..kernels.ops import jit_donating

    return _Programs(
        alloc=jit_donating(_alloc_impl, 3),
        reclaim=jit_donating(_reclaim_impl, 1),
        mask=jax.jit(_mask_impl),
        mask_batch=jax.jit(_mask_batch_impl),
        free_count=jax.jit(_free_count_impl),
        stripe_lanes=jax.jit(_stripe_lanes_impl,
                             static_argnames=("stripes",)),
        match=jax.jit(_match_impl, static_argnames=("ways",)),
        acquire_prefix=jit_donating(_acquire_prefix_impl, 1,
                                    static_argnames=("ways",)),
        insert_prefix=jit_donating(_insert_prefix_impl, 6,
                                   static_argnames=("ways",)),
        release_refs=jit_donating(_release_refs_impl, 1),
        orphan_plan=jax.jit(_orphan_plan_impl,
                            static_argnames=("stripes",)),
        scrub=jit_donating(_scrub_impl, 1),
        shared_stats=jax.jit(_shared_stats_impl),
        fold_hits=jit_donating(_fold_hits_impl, 1))


class KVPool:
    """Fixed pool of KV pages, map on device, reads under registry leases.

    ``registry`` may be shared with other subsystems (the engine passes the
    one registry whose table also serves the model-epoch lock — the paper's
    one-table-per-address-space economy); a private one is built if
    omitted.  ``map_slots`` sizes the prefix index (power of two; default
    4x the page count rounded up, one 4-way set per page — a tiny value
    forces slot collisions, which the property tests exploit)."""

    def __init__(self, n_pages: int, registry: Optional[BravoRegistry] = None,
                 stripes: int = 4, map_slots: int = 0,
                 metrics: Optional[MetricsRegistry] = None):
        if stripes < 1:
            raise ProtocolError(
                f"KVPool needs at least one lock stripe, got {stripes}")
        self.n_pages = n_pages
        self.registry = registry if registry is not None else BravoRegistry()
        self.stripes = stripes
        self.locks = [self.registry.alloc(name=f"kvstripe{s}")
                      for s in range(stripes)]
        # device mirror of stripe -> bias lane, for in-graph gathers
        self._stripe_idx = jnp.asarray([h.idx for h in self.locks], jnp.int32)
        self.owner = jnp.full((n_pages,), FREE, jnp.int32)
        if map_slots <= 0:
            # 4x the page count: at 4-way associativity that's one SET per
            # page, which holds the BENCH_slo Zipf trace's full-set
            # conflict rate under 0.05 (2x measured 0.12 — sets saturate
            # over a long trace because evicted requests leave their tail
            # entries cached).  Map metadata is five int32 vectors, so the
            # larger index costs 20 bytes per slot against a multi-KiB page.
            map_slots = 1
            while map_slots < 4 * n_pages:
                map_slots *= 2
        if map_slots & (map_slots - 1) != 0:
            raise ProtocolError(
                f"map_slots {map_slots} must be a power of two (the "
                f"prefix index masks hashes with map_slots - 1)")
        self.map_slots = map_slots
        # set-associativity: 4-way (or map_slots-way below 4 slots — a
        # 1-slot map degenerates to direct-mapped, which the forced-
        # collision property tests rely on)
        self.ways = min(4, map_slots)
        self._map_kh = jnp.zeros((map_slots,), jnp.int32)
        self._map_kl = jnp.zeros((map_slots,), jnp.int32)
        self._map_pg = jnp.full((map_slots,), -1, jnp.int32)
        self._map_ln = jnp.zeros((map_slots,), jnp.int32)
        self._map_age = jnp.zeros((map_slots,), jnp.int32)
        self._age_clock = 0           # monotonic insert stamp (host int)
        # per-page scale-metadata epoch (quantized pools): bumped when a
        # page is (re)allocated, so a stale scale is an observable state
        self.scale_gen = jnp.zeros((n_pages,), jnp.int32)
        self._mu = threading.Lock()   # guards the owner/map buffer swaps
        # bumped by every owner/map mutation: lets the engine cache a
        # slot's admission peek instead of re-syncing a device match on
        # every tick the slot stays blocked at the watermark
        self.version = 0
        # counters live on the shared metrics registry (defaulting to the
        # lock registry's, so a standalone pool and its stripes snapshot
        # as one namespace); properties keep the old attribute API
        self.metrics = (metrics if metrics is not None
                        else self.registry.metrics)
        self._c_lookups = self.metrics.counter("pool.lookups")
        self._c_allocates = self.metrics.counter("pool.allocates")
        self._c_reclaims = self.metrics.counter("pool.reclaims")
        self._c_prefix_lookups = self.metrics.counter("pool.prefix_lookups")
        # lookups that matched >= 1 page
        self._c_prefix_hits = self.metrics.counter("pool.prefix_hits")
        self._c_prefix_inserts = self.metrics.counter("pool.prefix_inserts")
        # per-key direct-mapped slot conflicts seen by lookups: the entry
        # in the slot belongs to a DIFFERENT key, so a would-be hit is
        # reported as a miss (ISSUE 9 satellite; baseline for the
        # set-associative rework in the ROADMAP)
        self._c_prefix_collisions = self.metrics.counter(
            "pool.prefix_collision")
        # device-resident dedup-hit accumulator: folded in-graph on every
        # traced prefix acquisition, harvested only in stats()
        self._dev_hits = jnp.zeros((), jnp.int32)

    # counter attribute compatibility (reads only; writes go through the
    # metrics registry so per-thread cells keep increments lock-free)
    @property
    def lookups(self) -> int:
        return self._c_lookups.value

    @property
    def allocates(self) -> int:
        return self._c_allocates.value

    @property
    def reclaims(self) -> int:
        return self._c_reclaims.value

    @property
    def prefix_lookups(self) -> int:
        return self._c_prefix_lookups.value

    @property
    def prefix_hits(self) -> int:
        return self._c_prefix_hits.value

    @property
    def prefix_inserts(self) -> int:
        return self._c_prefix_inserts.value

    @property
    def prefix_collisions(self) -> int:
        return self._c_prefix_collisions.value

    def _stripe(self, rid: int):
        return self.locks[rid % self.stripes]

    # -------------------------------------------------------------- readers
    def lookup(self, rid: int) -> List[int]:
        """PRIVATE pages owned by ``rid`` (shared prefix pages are tracked
        by the request's ref list, not the rid mask), read under the
        stripe's lease (control plane: the host-int rid costs one tiny
        upload, like the legacy path; the decode loop uses
        :meth:`lookup_batch` instead)."""
        h = self._stripe(rid)
        h.rearm()
        ids = jnp.asarray([rid], jnp.int32)
        granted = h.acquire(ids)
        try:
            with self._mu:
                mask = _programs().mask(self.owner,
                                        jnp.asarray(rid, jnp.int32))
                self._c_lookups.add(1)
            return list(np.where(np.asarray(mask))[0])
        finally:
            h.release(ids, granted=granted)

    def read_batch(self, rids: jax.Array):
        """Begin a leased batch read: ONE fused lease publish for the whole
        device-resident rid vector (stripe lanes gathered in-graph) plus
        one ownership mask — zero host sync.  Returns ``(token, mask)``;
        the leases stay PUBLISHED until :meth:`done_read_batch`, so a
        writer on any involved stripe drains until the read ends (this is
        what makes the lease a lock and not a counter)."""
        for h in self.locks:
            h.rearm()                 # host-clock check; dispatch only
        #                               when a stripe's window has passed
        lidx = _programs().stripe_lanes(self._stripe_idx, rids,
                                        stripes=self.stripes)
        granted = self.registry.acquire_by_index(lidx, rids)
        try:
            with self._mu:
                mask = _programs().mask_batch(self.owner, rids)
                self._c_lookups.add(1)
        except BaseException:         # never leak published leases
            self.registry.release_by_index(lidx, rids, granted)
            raise
        return (lidx, rids, granted), mask

    def done_read_batch(self, token) -> None:
        lidx, rids, granted = token
        self.registry.release_by_index(lidx, rids, granted)

    def lookup_batch(self, rids: jax.Array) -> jax.Array:
        """Point-in-time batch read (mask only; leases released before
        returning — use :meth:`read_batch` to hold them across work)."""
        token, mask = self.read_batch(rids)
        self.done_read_batch(token)
        return mask

    # -------------------------------------------------------------- writers
    def allocate_async(self, rid: int, n: int, **revoke_kw):
        """Dispatch-only first-fit allocate: revoke the rid's stripe bias,
        drain its readers, and enqueue the donated owner-vector update —
        WITHOUT synchronizing on the result.  Returns device ``(take
        mask, enough)``; pass to :meth:`materialize_alloc` for the page
        indices.  Callers holding a host write lock (``PageTable``) drop
        it between the two calls, so the host-device sync never extends
        the writer's critical section — which is exactly the BRAVO
        revocation window every other reader pays for.  Taking a cached-
        free page evicts its prefix entry in the same program."""
        self._stripe(rid).revoke(**revoke_kw)
        with self._mu:
            owner, map_pg, scale_gen, take, ok = _programs().alloc(
                self.owner, self._map_pg, self.scale_gen,
                jnp.asarray(rid, jnp.int32), jnp.asarray(n, jnp.int32))
            self.owner = owner
            self._map_pg = map_pg
            self.scale_gen = scale_gen
            self._c_allocates.add(1)
            self.version += 1
        if _TR.enabled:
            _TR.emit("pool", "alloc", rid=rid, n=n)
        return take, ok

    @staticmethod
    def materialize_alloc(take, ok) -> List[int]:
        """Synchronizing half of :meth:`allocate_async` (all-or-nothing;
        [] when the pool was short)."""
        if not bool(ok):
            return []
        return np.where(np.asarray(take))[0].tolist()

    def allocate(self, rid: int, n: int, **revoke_kw) -> List[int]:
        """First-fit allocate ``n`` pages to ``rid`` (all-or-nothing; []
        when the pool is short).  Revokes ONLY this rid's stripe bias —
        reads on other stripes keep their fast path throughout."""
        return self.materialize_alloc(*self.allocate_async(rid, n,
                                                           **revoke_kw))

    def reclaim_async(self, rid: int, **revoke_kw) -> jax.Array:
        """Dispatch-only reclaim of ``rid``'s PRIVATE pages; returns the
        device count (``int()`` it after dropping any write lock).  Shared
        pages the request holds refs on go through :meth:`release_refs`."""
        self._stripe(rid).revoke(**revoke_kw)
        with self._mu:
            owner, cnt = _programs().reclaim(self.owner,
                                             jnp.asarray(rid, jnp.int32))
            self.owner = owner
            self._c_reclaims.add(1)
            self.version += 1
        if _TR.enabled:
            _TR.emit("pool", "reclaim", rid=rid)
        return cnt

    def reclaim(self, rid: int, **revoke_kw) -> int:
        return int(self.reclaim_async(rid, **revoke_kw))

    # ------------------------------------------------------- prefix caching
    def match_prefix(self, kh, kl, ln):
        """Peek the prefix index (no refs taken): -> (per-key page list,
        usable run length, per-key refcount-0 flags — a hit on such a key
        consumes a free page when acquired).  SYNCHRONIZES; admission-
        control plane only.  Key vectors come from :func:`page_keys`."""
        with self._mu:
            pages, n_run, free_hit, n_coll = _programs().match(
                self.owner, self._map_kh, self._map_kl, self._map_pg,
                self._map_ln, jnp.asarray(kh), jnp.asarray(kl),
                jnp.asarray(ln), ways=self.ways)
            self._c_prefix_lookups.add(1)
        n = int(n_run)                # sync OUTSIDE the mutex: a writer's
        if n > 0:                     # dispatch must never queue behind a
            self._c_prefix_hits.add(1)  # reader's host round-trip
        c = int(n_coll)               # full-set conflicts: would-be hits
        if c > 0:                     # turned into misses (PR-9 measured
            self._c_prefix_collisions.add(c)  # 0.47 direct-mapped)
        if _TR.enabled:
            _TR.emit("pool", "dedup_hit" if n > 0 else "dedup_miss", run=n,
                     collisions=c)
        return np.asarray(pages).tolist(), n, np.asarray(free_hit).tolist()

    def acquire_prefix_async(self, kh, kl, ln, take):
        """Dispatch-only ref acquisition on the hit run's pages selected by
        the bool ``take`` mask (the caller's share-by-ref prefix plus the
        one copy-on-write source, which it releases again after copying).
        No stripe revocation: refcounts never touch a live rid's mask or
        any page a reader currently addresses."""
        with self._mu:
            owner, pages, revived = _programs().acquire_prefix(
                self.owner, self._map_kh, self._map_kl, self._map_pg,
                self._map_ln, jnp.asarray(kh), jnp.asarray(kl),
                jnp.asarray(ln), jnp.asarray(take), ways=self.ways)
            self.owner = owner
            self.version += 1
            if _TR.enabled:
                # device-resident fold: counts the hit pages in-graph,
                # nothing crosses the host boundary on this path
                self._dev_hits = _programs().fold_hits(self._dev_hits,
                                                       pages)
        if _TR.enabled:
            _TR.emit("pool", "ref_acquire")
        return pages, revived

    @staticmethod
    def materialize_prefix(pages, revived) -> Tuple[List[int], int]:
        return np.asarray(pages).tolist(), int(revived)

    def acquire_prefix(self, kh, kl, ln, take) -> Tuple[List[int], int]:
        return self.materialize_prefix(*self.acquire_prefix_async(
            kh, kl, ln, take))

    def insert_prefix_async(self, rid: int, kh, kl, ln, lane_pages):
        """Dispatch-only index publish for a request whose prompt pages
        are fully written: each key's page converts from ``rid``-private
        to shared-refcount-1 where the map slot is free.  Returns the
        converted mask (device)."""
        with self._mu:
            self._age_clock += 1
            (owner, mkh, mkl, mpg, mln, mage, ins) = \
                _programs().insert_prefix(
                    self.owner, self._map_kh, self._map_kl, self._map_pg,
                    self._map_ln, self._map_age, jnp.asarray(kh),
                    jnp.asarray(kl), jnp.asarray(ln),
                    jnp.asarray(lane_pages), jnp.asarray(rid, jnp.int32),
                    jnp.asarray(self._age_clock, jnp.int32),
                    ways=self.ways)
            self.owner = owner
            self._map_kh, self._map_kl = mkh, mkl
            self._map_pg, self._map_ln = mpg, mln
            self._map_age = mage
            self._c_prefix_inserts.add(1)
            self.version += 1
        if _TR.enabled:
            _TR.emit("pool", "prefix_insert", rid=rid)
        return ins

    def insert_prefix(self, rid: int, kh, kl, ln, lane_pages) -> List[bool]:
        return np.asarray(self.insert_prefix_async(
            rid, kh, kl, ln, lane_pages)).tolist()

    def release_refs_async(self, pages) -> jax.Array:
        """Dispatch-only ref release for a (-1-padded) page vector; a page
        reaching refcount 0 becomes free-but-cached.  Returns the device
        count of pages freed."""
        with self._mu:
            owner, freed = _programs().release_refs(
                self.owner, jnp.asarray(pages, jnp.int32))
            self.owner = owner
            self.version += 1
        if _TR.enabled:
            _TR.emit("pool", "ref_release")
        return freed

    def release_refs(self, pages) -> int:
        return int(self.release_refs_async(pages))

    # ---------------------------------------------------------- compaction
    def orphan_plan(self, live: jax.Array):
        """Count orphan pages (owner not in the -1-padded ``live`` rid
        vector, free, or refcount-held): -> (per-stripe counts np, total
        int).  SYNCHRONIZES — call it before taking any write lock; the
        scrub recheck runs in graph, so a stale plan only ever skips or
        over-revokes stripes, never frees a live page."""
        with self._mu:
            per, total = _programs().orphan_plan(self.owner, live,
                                                 stripes=self.stripes)
        return np.asarray(per), int(total)

    def scrub_orphans_async(self, live: jax.Array,
                            stripe_mask=None, **revoke_kw) -> jax.Array:
        """Dispatch-only orphan scrub: revoke (and drain) only the stripes
        the plan flagged, then enqueue the donated owner update.  A page
        with ``refcount > 0`` is never scrubbed, whoever its holders are.
        Returns the device count of pages freed."""
        for s, h in enumerate(self.locks):
            if stripe_mask is None or stripe_mask[s]:
                h.revoke(**revoke_kw)
        with self._mu:
            owner, cnt = _programs().scrub(self.owner, live)
            self.owner = owner
            self._c_reclaims.add(1)
            self.version += 1
        if _TR.enabled:
            _TR.emit("pool", "orphan_scrub")
        return cnt

    # ---------------------------------------------------------------- misc
    def free_pages(self) -> List[int]:
        """Free page indices (synchronizing; off the hot path)."""
        with self._mu:
            return list(np.where(np.asarray(self.owner) == FREE)[0])

    def free_count(self) -> int:
        with self._mu:
            return int(_programs().free_count(self.owner))

    def stats(self) -> dict:
        with self._mu:
            shared, refs, entries = (int(x) for x in _programs()
                                     .shared_stats(self.owner, self._map_pg))
        return {"n_pages": self.n_pages, "stripes": self.stripes,
                "free": self.free_count(), "lookups": self.lookups,
                "allocates": self.allocates, "reclaims": self.reclaims,
                "shared_pages": shared, "refcount_total": refs,
                "cached_entries": entries, "map_slots": self.map_slots,
                "map_ways": self.ways,
                "prefix_lookups": self.prefix_lookups,
                "prefix_hits": self.prefix_hits,
                "prefix_inserts": self.prefix_inserts,
                "prefix_collisions": self.prefix_collisions,
                # harvest of the device-resident fold (counts only while
                # tracing was enabled; zero otherwise)
                "dedup_pages_hit": int(self._dev_hits)}
