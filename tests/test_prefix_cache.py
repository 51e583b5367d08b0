"""PR 5: streaming chunk-prefill kernel + device-side prefix-cache page
dedup.  Covers the chunk kernel against its bit-exact oracle and the dense
formulation, a property sweep of insert -> lookup -> COW -> reclaim
round-trips against a host model (tiny map: slot collisions guaranteed),
and token-for-token paged-prefill-vs-dense equivalence with and without
cache hits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.core.registry import BravoRegistry
from repro.dist.sharding import MeshRules
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_pool import FREE, KVPool, page_keys
from repro.serving.scheduler import SchedulerConfig
from repro.serving.steps import make_decode_step

SLOTS = 1024


def mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_smoke("llama3.2-1b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# Chunk kernel vs oracle
# ---------------------------------------------------------------------------


def _random_chunk_case(rng, b, s, h, kvh, hd, n_pages, ps, lanes,
                       pad_rows=1):
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, kvh, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, kvh, hd)), jnp.float32)
    page_idx = np.full((b, lanes), -1, np.int32)
    cache_len = np.zeros((b,), np.int32)
    new_lens = np.zeros((b,), np.int32)
    perm = rng.permutation(n_pages)
    off = 0
    for i in range(b - pad_rows):
        nl = int(rng.integers(1, s + 1))
        clen = int(rng.integers(nl, lanes * ps + 1))
        npg = -(-clen // ps)
        page_idx[i, :npg] = perm[off:off + npg]
        off += npg
        cache_len[i] = clen
        new_lens[i] = nl
    return q, kp, vp, map(jnp.asarray, (page_idx, cache_len, new_lens))


def test_chunk_kernel_bit_exact_vs_ref():
    """The streaming kernel equals its oracle bit for bit (same (row,
    q-block, page) walk, both under jit), with mid-prompt chunks, partial
    chunks and fully padded rows in one batch."""
    rng = np.random.default_rng(0)
    q, kp, vp, (pi, cl, nl) = _random_chunk_case(
        rng, b=5, s=8, h=8, kvh=2, hd=16, n_pages=32, ps=4, lanes=6)
    out_k = np.asarray(K.paged_chunk_attention(q, kp, vp, pi, cl, nl))
    out_r = np.asarray(jax.jit(R.paged_chunk_attn_ref)(q, kp, vp, pi, cl,
                                                       nl))
    assert np.array_equal(out_k, out_r)
    assert np.array_equal(out_k[-1], np.zeros_like(out_k[-1]))  # pad row


def test_chunk_kernel_matches_dense_gather():
    """Streaming == the PR-4 dense gather path (full softmax over densely
    materialized pages), up to float tolerance — the two sides of the
    benchmark's streamed-vs-dense comparison agree."""
    rng = np.random.default_rng(1)
    q, kp, vp, (pi, cl, nl) = _random_chunk_case(
        rng, b=4, s=6, h=4, kvh=2, hd=8, n_pages=16, ps=4, lanes=4)
    out_k = np.asarray(K.paged_chunk_attention(q, kp, vp, pi, cl, nl))
    dense = np.asarray(jax.jit(R.paged_chunk_dense_ref)(q, kp, vp, pi, cl,
                                                        nl))
    assert np.allclose(out_k, dense, atol=1e-5)


def test_chunk_kernel_multi_qblock_grid():
    """A chunk wider than the q-block limit spans several q-blocks in the
    grid and still matches the oracle bit for bit."""
    rng = np.random.default_rng(2)
    q, kp, vp, (pi, cl, nl) = _random_chunk_case(
        rng, b=2, s=64, h=4, kvh=2, hd=8, n_pages=64, ps=8, lanes=10,
        pad_rows=0)
    out_k = np.asarray(K.paged_chunk_attention(q, kp, vp, pi, cl, nl))
    out_r = np.asarray(jax.jit(R.paged_chunk_attn_ref)(q, kp, vp, pi, cl,
                                                       nl))
    assert np.array_equal(out_k, out_r)


# ---------------------------------------------------------------------------
# Prefix-index property sweep vs a host model (tiny map: collisions forced)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False


class HostModel:
    """Pure-python mirror of the pool's owner encoding + set-associative
    prefix index (min(4, map_slots)-way sets, keys inserted in lane order,
    oldest-entry eviction among EARLIER inserts' entries when a set is
    full — same tie-breaking as the device program: lowest way among
    vacants / among minimal ages); the sweep checks the device state
    against it after every operation."""

    def __init__(self, n_pages, map_slots):
        self.owner = np.full(n_pages, FREE, np.int64)
        self.map = {}          # absolute slot -> (kh, kl, ln, page)
        self.age = {}          # absolute slot -> insert stamp
        self.map_slots = map_slots
        self.ways = min(4, map_slots)
        self.n_sets = map_slots // self.ways
        self.clock = 0

    def cached(self):
        return {p for (_, _, _, p) in self.map.values()}

    def alloc(self, rid, n):
        free = [p for p in range(len(self.owner)) if self.owner[p] == FREE]
        plain = [p for p in free if p not in self.cached()]
        cach = [p for p in free if p in self.cached()]
        if len(free) < n:
            return []
        take = (plain + cach)[:n]
        for p in take:
            self.owner[p] = rid
        for s in [s for s, e in self.map.items() if e[3] in take]:
            del self.map[s]
        return sorted(take)

    def reclaim(self, rid):
        mine = [p for p in range(len(self.owner)) if self.owner[p] == rid]
        self.owner[mine] = FREE
        return len(mine)

    def _set_slots(self, kl):
        set_i = int(kl) & (self.n_sets - 1)
        return [set_i * self.ways + w for w in range(self.ways)]

    def match(self, kh, kl, ln):
        pages, run = [], True
        for i in range(len(kh)):
            page = -1
            if ln[i] > 0:
                for s in self._set_slots(kl[i]):
                    e = self.map.get(s)
                    if (e is not None and e[0] == kh[i] and e[1] == kl[i]
                            and e[2] == ln[i]):
                        page = e[3]
                        break
            run = run and page >= 0
            pages.append(page if run else -1)
        return pages, sum(p >= 0 for p in pages)

    def acquire(self, kh, kl, ln, take):
        pages, _ = self.match(kh, kl, ln)
        out = []
        for i, p in enumerate(pages):
            if p >= 0 and take[i]:
                self.owner[p] -= 1           # refcount++
                out.append(p)
            else:
                out.append(-1)
        return out

    def insert(self, rid, kh, kl, ln, lane_pg):
        self.clock += 1
        valid = [ln[i] > 0 and lane_pg[i] >= 0
                 and self.owner[lane_pg[i]] == rid for i in range(len(kh))]
        ins = []
        for i in range(len(kh)):
            slots = self._set_slots(kl[i])
            present = any(
                s in self.map and self.map[s][:3]
                == (int(kh[i]), int(kl[i]), int(ln[i])) for s in slots)
            vac = [s for s in slots if s not in self.map]
            # never evict an entry of this same insert (a chain hole)
            older = [s for s in slots
                     if s in self.map and self.age[s] < self.clock]
            ok = valid[i] and not present and bool(vac or older)
            if ok:
                slot = vac[0] if vac else min(
                    older, key=lambda s: (self.age[s], s))
                self.map[slot] = (int(kh[i]), int(kl[i]), int(ln[i]),
                                  int(lane_pg[i]))
                self.age[slot] = self.clock
                self.owner[lane_pg[i]] = -2
            ins.append(ok)
        return ins

    def release(self, pages):
        freed = 0
        for p in pages:
            if p >= 0 and self.owner[p] <= -2:
                self.owner[p] += 1
                freed += self.owner[p] == FREE
        return freed


def _assert_mirror(pool, model):
    assert np.array_equal(np.asarray(pool.owner), model.owner), \
        (np.asarray(pool.owner), model.owner)
    pg = np.asarray(pool._map_pg)
    want = np.full(pool.map_slots, -1, np.int64)
    for s, e in model.map.items():
        want[s] = e[3]
    assert np.array_equal(pg, want), (pg, want)


def _run_prefix_sweep(prompts, seed):
    """Drive the engine's admission policy (match -> cap -> acquire ->
    alloc -> COW-release -> insert -> teardown) through the pool AND the
    host model, comparing device state after every step.  map_slots=8
    guarantees slot collisions across a few distinct prompts."""
    ps, lanes, n_pages, map_slots = 4, 4, 24, 8
    pool = KVPool(n_pages, registry=BravoRegistry(slots=SLOTS),
                  stripes=2, map_slots=map_slots)
    model = HostModel(n_pages, map_slots)
    rng = np.random.default_rng(seed)
    live = []      # (rid, refs, tail_cow_done)
    next_rid = 0
    for tok_seed in prompts:
        # teardown a random live request first, sometimes
        if live and rng.random() < 0.4:
            rid, refs = live.pop(int(rng.integers(len(live))))
            assert pool.release_refs(np.asarray(refs + [-1], np.int32)) \
                == model.release(refs + [-1])
            assert pool.reclaim(rid) == model.reclaim(rid)
            _assert_mirror(pool, model)
        n = len(tok_seed)
        kh, kl, ln = page_keys(tok_seed, ps, pad_to=lanes)
        got = pool.match_prefix(kh, kl, ln)
        want_pages, want_run = model.match(kh, kl, ln)
        assert got[0] == want_pages and got[1] == want_run
        cov = min(int(np.sum(ln[:want_run])), n - 1)
        k_ref = cov // ps
        cow = cov % ps > 0
        take = np.zeros(lanes, bool)
        take[:k_ref + (1 if cow else 0)] = True
        hit, _ = pool.acquire_prefix(kh, kl, ln, take)
        assert hit == model.acquire(kh, kl, ln, take)
        _assert_mirror(pool, model)
        rid = next_rid
        next_rid += 1
        total = -(-(n + 1) // ps)
        pages = pool.allocate(rid, total - k_ref)
        assert pages == model.alloc(rid, total - k_ref)
        _assert_mirror(pool, model)
        refs = [p for p in hit[:k_ref] if p >= 0]
        if not pages:               # pool short: undo like the engine
            got_refs = refs + ([hit[k_ref]] if cow else [])
            if got_refs:
                assert pool.release_refs(np.asarray(got_refs, np.int32)) \
                    == model.release(got_refs)
            _assert_mirror(pool, model)
            continue
        if cow:                     # release the transient COW-source ref
            assert pool.release_refs(np.asarray([hit[k_ref]], np.int32)) \
                == model.release([hit[k_ref]])
            _assert_mirror(pool, model)
        lane_list = refs + pages
        n_keys = int(np.sum(ln > 0))
        lane_pg = np.full(lanes, -1, np.int32)
        lane_pg[:n_keys] = lane_list[:n_keys]
        ins = pool.insert_prefix(rid, kh, kl, ln, lane_pg)
        assert ins[:n_keys] == model.insert(rid, kh, kl, ln, lane_pg)[:n_keys]
        _assert_mirror(pool, model)
        refs = refs + [int(lane_pg[i]) for i in range(n_keys) if ins[i]]
        live.append((rid, refs))
    # drain everything: refcounts must balance to zero
    for rid, refs in live:
        assert pool.release_refs(np.asarray(refs + [-1], np.int32)) \
            == model.release(refs + [-1])
        assert pool.reclaim(rid) == model.reclaim(rid)
    _assert_mirror(pool, model)
    owner = np.asarray(pool.owner)
    assert (owner == FREE).all(), owner        # nothing leaked
    assert pool.free_count() == n_pages


def _prompt(seed, length):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 4, size=length).astype(np.int32)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 14)),
                    min_size=1, max_size=8),
           st.integers(0, 2**31 - 1))
    def test_prefix_roundtrip_properties(specs, seed):
        _run_prefix_sweep([_prompt(s, l) for s, l in specs], seed)
else:                                                     # pragma: no cover
    @pytest.mark.parametrize("case", range(15))
    def test_prefix_roundtrip_properties(case):
        rng = np.random.default_rng(case)
        specs = [(int(rng.integers(0, 4)), int(rng.integers(1, 15)))
                 for _ in range(int(rng.integers(1, 9)))]
        _run_prefix_sweep([_prompt(s, l) for s, l in specs], case)


def test_forced_set_conflict_evicts_oldest_never_corrupts():
    """Two different prefixes whose keys land in the same (1-way) set:
    the second insert evicts the older ENTRY by age — the victim page's
    owner/refcount state is untouched (its sharers keep their refs; the
    page just stops serving new hits), and neither key ever false-hits
    the other's entry.  A set conflict degrades dedup, never
    correctness."""
    ps = 4
    pool = KVPool(8, registry=BravoRegistry(slots=SLOTS), stripes=1,
                  map_slots=1)             # 1-way: EVERY key shares set 0
    a = np.asarray([1, 2, 3, 4], np.int32)
    b = np.asarray([9, 8, 7, 6], np.int32)
    ka = page_keys(a, ps, pad_to=2)
    kb = page_keys(b, ps, pad_to=2)
    pa = pool.allocate(0, 1)
    assert pool.insert_prefix(0, *ka, np.asarray(pa + [-1], np.int32))[0]
    assert pool.match_prefix(*ka)[1] == 1      # A served while cached
    pb = pool.allocate(1, 1)
    # B's insert finds the set full and evicts A's (older) entry
    assert pool.insert_prefix(1, *kb, np.asarray(pb + [-1], np.int32))[0]
    assert pool.match_prefix(*kb)[1] == 1      # B now served
    assert pool.match_prefix(*ka)[1] == 0      # A misses; no false hit
    assert pool.prefix_collisions >= 1         # ...and counts the conflict
    # eviction dropped only the map entry: A's page keeps its inserter
    # ref (shared, refcount 1) until A releases it
    assert np.asarray(pool.owner)[pa[0]] == -2
    assert pool.release_refs(np.asarray(pa, np.int32)) == 1
    assert np.asarray(pool.owner)[pa[0]] == FREE


# ---------------------------------------------------------------------------
# Engine equivalence: dedup on, with and without hits, token for token
# ---------------------------------------------------------------------------


def dense_reference(cfg, params, prompt, max_new):
    mesh, rules = mesh1(), MeshRules()
    decode = jax.jit(make_decode_step(cfg, mesh, rules))
    caches = M.init_caches(cfg, 1, 64, dtype=jnp.bfloat16)
    s = len(prompt)
    out = []
    cur = jnp.asarray(prompt[:1][None])
    for step in range(s - 1 + max_new):
        clen = jnp.full((1,), step + 1, jnp.int32)
        nxt, _, caches = decode(params, caches, cur, clen)
        if step + 1 < s:
            cur = jnp.asarray(prompt[step + 1:step + 2][None])
        else:
            cur = nxt
            out.append(int(np.asarray(nxt)[0, 0]))
    return out


def _serve(cfg, params, prompts, max_new, sc, n_pages, warm=0):
    eng = ServingEngine(cfg, params, mesh=mesh1(), rules=MeshRules(),
                        n_pages=n_pages, scheduler=sc)
    eng.start()
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs[:warm]:                  # sequential: cache fills first
        eng.submit(r)
        assert r.done.wait(timeout=600)
    for r in reqs[warm:]:
        eng.submit(r)
    for r in reqs:
        assert r.done.wait(timeout=600), "request timed out"
    eng.stop()
    return eng, [list(r.out) for r in reqs]


def test_multichunk_prefill_with_and_without_hits(smoke_model):
    """THE acceptance scenario: multi-chunk prompts (13 > chunk of 4)
    served cold (no cache), then warm (identical prompt: full-page hits +
    a COW boundary), then diverging mid-prompt (partial hit) — every
    output token equals the dense path's, and the warm requests provably
    rode the cache."""
    cfg, params = smoke_model
    base = np.arange(1, 15, dtype=np.int32)          # 14 tokens, 4 chunks
    div = base.copy()
    div[6] = 99                                      # diverges in page 1
    max_new = 4
    want = {p.tobytes(): dense_reference(cfg, params, p, max_new)
            for p in (base, div)}
    sc = SchedulerConfig(max_slots=2, page_size=4, max_seq=32,
                         prefill_chunk=4, prefill_rows=2, token_budget=8)
    eng, got = _serve(cfg, params, [base, base, div], max_new,
                      sc, n_pages=64, warm=1)
    assert got[0] == want[base.tobytes()], (got[0], want[base.tobytes()])
    assert got[1] == want[base.tobytes()]
    assert got[2] == want[div.tobytes()]
    st = eng.lock_stats()
    assert st["engine"]["pages_saved"] >= 4     # warm: 3 full; div: page 0
    # warm coverage is 14 capped to 13 — mid-page, so the boundary page is
    # copied, never written through
    assert st["engine"]["cow_copies"] >= 1
    assert st["engine"]["cached_tokens"] >= 13 + 4
    # refcounts balance to zero after drain; cache entries may remain
    assert st["kv_pool"]["refcount_total"] == 0
    assert st["kv_pool"]["shared_pages"] == 0
    assert st["kv_pool"]["free"] == 64


def test_prefix_cache_off_matches_on(smoke_model):
    """prefix_cache=False serves the same tokens (and never consults the
    index)."""
    cfg, params = smoke_model
    base = np.arange(3, 12, dtype=np.int32)
    sc_off = SchedulerConfig(max_slots=2, page_size=4, max_seq=32,
                             prefill_chunk=4, prefill_rows=2,
                             token_budget=8, prefix_cache=False)
    eng, got = _serve(cfg, params, [base, base], 3, sc_off,
                      n_pages=64, warm=1)
    assert got[0] == got[1] == dense_reference(cfg, params, base, 3)
    assert eng.kv_pool.prefix_lookups == 0
    assert eng.stats.pages_saved == 0


def test_evicted_sharer_preserves_survivor_output(smoke_model):
    """Page pressure evicts requests that share prefix pages; the
    refcounts keep every survivor's pages alive and all outputs still
    equal the dense path (the engine-level face of the pool-level
    preemption regression test)."""
    cfg, params = smoke_model
    base = np.arange(1, 10, dtype=np.int32)
    prompts = [base, base, base.copy()]
    max_new = 6
    want = dense_reference(cfg, params, base, max_new)
    sc = SchedulerConfig(max_slots=3, page_size=4, max_seq=32,
                         prefill_chunk=8, prefill_rows=2, token_budget=16)
    eng, got = _serve(cfg, params, prompts, max_new, sc,
                      n_pages=5, warm=1)      # tight pool: forces eviction
    assert got == [want] * 3, (got, want)
    assert eng.scheduler.evictions >= 1, "pool was sized to force eviction"
    st = eng.lock_stats()
    assert st["engine"]["pages_saved"] >= 2   # sharing really happened
    assert st["kv_pool"]["refcount_total"] == 0
    assert st["kv_pool"]["free"] == 5
