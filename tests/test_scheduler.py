"""Continuous-batching scheduler + paged-attention decode: kernel-vs-ref,
FSM policy, paged-vs-dense token equivalence (single- and multi-host
meshes), chunked prefill, page-pressure eviction, and the engine/scheduler
split's lock guarantees."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.core import LiveMem, LockEnv
from repro.dist.sharding import MeshRules
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.models import model as M
from repro.serving.engine import PageTable, Request, ServingEngine
from repro.serving.kv_pool import KVPool
from repro.serving.scheduler import (Phase, Scheduler, SchedulerConfig,
                                     SlotState)
from repro.serving.steps import make_decode_step, make_paged_prefill_step


def mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def mesh2d():
    """The multi-pod ("pod", "data") axis layout of the dry-run topology
    (1 device per axis on the CPU validation backend)."""
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_smoke("llama3.2-1b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def dense_reference(cfg, params, prompt: np.ndarray, max_new: int):
    """Single-host dense-cache decode (the pre-scheduler data plane):
    token-by-token against ``init_caches``, B = 1."""
    mesh, rules = mesh1(), MeshRules()
    decode = jax.jit(make_decode_step(cfg, mesh, rules))
    caches = M.init_caches(cfg, 1, 32, dtype=jnp.bfloat16)
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


# ---------------------------------------------------------------------------
# Kernel vs oracle
# ---------------------------------------------------------------------------


def test_paged_attn_kernel_bit_exact_vs_ref():
    rng = np.random.default_rng(0)
    b, h, kvh, hd, n_pages, ps, lanes = 5, 8, 2, 16, 32, 4, 6
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
    cache_len[3] = 0                       # inactive slot -> zeros out
    pi, cl = jnp.asarray(page_idx), jnp.asarray(cache_len)
    store = R.one_layer(kp), R.one_layer(vp), jnp.int32(0)
    out_k = np.asarray(K.paged_attention(q, *store, pi, cl))
    out_r = np.asarray(jax.jit(R.paged_attn_ref)(q, *store, pi, cl))
    assert np.array_equal(out_k, out_r)    # bit-exact, same page-walk order
    assert np.array_equal(out_k[3], np.zeros_like(out_k[3]))


def test_paged_attn_matches_dense_softmax():
    """The online-softmax page walk equals full-softmax attention over the
    densely gathered pages (up to float tolerance)."""
    from repro.models.common import decode_attention

    rng = np.random.default_rng(1)
    b, h, kvh, hd, n_pages, ps, lanes = 3, 4, 2, 8, 16, 4, 4
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, ps, kvh, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, ps, kvh, hd)), jnp.float32)
    page_idx = np.asarray([[0, 1, 2, 3], [4, 5, -1, -1], [6, -1, -1, -1]],
                          np.int32)
    cache_len = np.asarray([15, 7, 3], np.int32)
    out = np.asarray(K.paged_attention(
        q, R.one_layer(kp), R.one_layer(vp), jnp.int32(0),
        jnp.asarray(page_idx), jnp.asarray(cache_len)))
    kd = np.zeros((b, lanes * ps, kvh, hd), np.float32)
    vd = np.zeros((b, lanes * ps, kvh, hd), np.float32)
    for i in range(b):
        for p in range(lanes):
            if page_idx[i, p] >= 0:
                kd[i, p * ps:(p + 1) * ps] = np.asarray(kp)[page_idx[i, p]]
                vd[i, p * ps:(p + 1) * ps] = np.asarray(vp)[page_idx[i, p]]
    dense = np.asarray(decode_attention(
        q[:, None], jnp.asarray(kd), jnp.asarray(vd),
        jnp.asarray(cache_len)))[:, 0]
    assert np.allclose(out, dense, atol=1e-5)


# ---------------------------------------------------------------------------
# Scheduler policy (pure FSM, no jax)
# ---------------------------------------------------------------------------


def make_slot(rid, n_prompt=6, max_new=4):
    return SlotState(rid=rid, prefix=np.arange(1, n_prompt + 1, dtype=np.int32),
                     max_new=max_new)


def test_fsm_admission_watermarks_and_interleave():
    cfg = SchedulerConfig(max_slots=2, page_size=4, max_seq=32,
                          prefill_chunk=4, prefill_rows=2, token_budget=8,
                          admit_free_frac=0.25)
    sched = Scheduler(cfg, n_pages=16)
    for i in range(4):
        sched.submit(make_slot(i))
    # slot cap: only 2 of 4 admitted despite ample pages
    admitted = sched.admit(free_pages=16)
    assert [s.rid for s in admitted] == [0, 1]
    assert all(s.phase is Phase.PREFILL for s in admitted)
    # page watermark: each needs 2 pages; floor is 4 -> only one more fits
    # once a row frees up, and none when free_pages is at the floor
    assert sched.admit(free_pages=4) == []
    # prefill plan: chunked to prefill_chunk, oldest first, budget-capped
    plan = sched.plan()
    assert plan.kind == "prefill" and plan.chunks == [4, 4]
    for st, c in zip(plan.slots, plan.chunks):
        assert not sched.on_prefill(st, c)       # 6-token prompt: mid-way
    plan = sched.plan()                          # no decode yet -> prefill
    assert plan.kind == "prefill" and plan.chunks == [2, 2]
    for st, c in zip(plan.slots, plan.chunks):
        assert sched.on_prefill(st, c)           # done -> DECODE
        assert st.phase is Phase.DECODE
        sched.on_token(st, 7)
    # decode/prefill interleave: with decode work live, at most one
    # prefill tick per decode_ticks_per_prefill
    sched.submit(make_slot(9))
    assert len(sched.admit(free_pages=16)) == 0  # rows full
    assert sched.plan().kind == "decode"


def test_fsm_finish_and_eviction_requeue():
    cfg = SchedulerConfig(max_slots=2, page_size=4, max_seq=32,
                          prefill_chunk=8, prefill_rows=2, token_budget=16)
    sched = Scheduler(cfg, n_pages=8)
    a, b = make_slot(0, max_new=2), make_slot(1, max_new=4)
    sched.submit(a)
    sched.submit(b)
    sched.admit(free_pages=8)
    for st in (a, b):
        assert sched.on_prefill(st, 6)
        sched.on_token(st, 100)
    assert sched.on_token(a, 101)            # a hits max_new
    sched.finish(a)
    assert a.phase is Phase.DONE and a.row == -1
    # eviction folds generated tokens into the prefix and requeues at head
    victim = sched.pick_victim()
    assert victim is b
    sched.evict(b)
    assert b.phase is Phase.EVICTED and b.evictions == 1
    assert list(b.prefix[-1:]) == [100] and b.prefill_pos == 0
    assert sched.waiting[0] is b
    readmitted = sched.admit(free_pages=8)
    assert readmitted == [b]
    # re-prefill covers prompt + generated; remaining max_new unchanged
    assert b.n_prefix == 7 and len(b.out) == 1 and b.max_new == 4


def test_fsm_growth_flags_page_boundary():
    cfg = SchedulerConfig(max_slots=1, page_size=4, max_seq=32,
                          prefill_chunk=8, prefill_rows=1, token_budget=8)
    sched = Scheduler(cfg, n_pages=8)
    st = make_slot(0, n_prompt=3, max_new=8)
    sched.submit(st)
    sched.admit(free_pages=8)
    st.pages = [5]                           # covers positions 0..3
    sched.on_prefill(st, 3)
    sched.on_token(st, 9)                    # pos=4: next write at 3 -> fits
    assert sched.plan().grow == []
    sched.on_token(st, 9)                    # pos=5: next write at 4 -> grow
    assert sched.plan().grow == [st]


# ---------------------------------------------------------------------------
# Paged data plane == dense data plane, token for token
# ---------------------------------------------------------------------------


def run_engine(cfg, params, mesh, prompts, max_new, sched_cfg, n_pages,
               **start_kw):
    eng = ServingEngine(cfg, params, mesh=mesh, rules=MeshRules(),
                        n_pages=n_pages, scheduler=sched_cfg)
    eng.start(**start_kw)
    reqs = [Request(rid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    for r in reqs:
        assert r.done.wait(timeout=600), "request timed out"
    eng.stop()
    return eng, [list(r.out) for r in reqs]


def test_scheduler_engine_matches_dense_on_2d_mesh(smoke_model):
    """THE acceptance scenario: scheduler-driven paged decode on the
    multi-pod ("pod", "data") mesh produces token-for-token identical
    output to the single-host dense-cache path, while a mid-schedule
    weight swap (identity perturb: same logits, full revocation protocol)
    runs — and never flaps the KV stripes' bias."""
    cfg, params = smoke_model
    prompts = [np.arange(1, 6, dtype=np.int32) + i for i in range(3)]
    max_new = 4
    want = [dense_reference(cfg, params, p, max_new) for p in prompts]
    sc = SchedulerConfig(max_slots=4, page_size=4, max_seq=32,
                         prefill_chunk=8, prefill_rows=2, token_budget=16)
    eng, got = run_engine(cfg, params, mesh2d(), prompts, max_new, sc,
                          n_pages=64, swap_period_s=0.05,
                          perturb=lambda p: p)
    assert got == want, (got, want)
    st = eng.lock_stats()
    assert st["engine"]["weight_swaps"] >= 1
    assert st["scheduler"]["finished"] == 3
    assert eng.kv_pool.free_count() == 64


def test_weight_swap_never_flaps_kv_stripe_bias(smoke_model):
    """A model-epoch revocation clears ONLY the model lock's bias lane —
    the KV stripes' armed state is untouched (the per-lock registry fix,
    now load-bearing for the scheduler's hot path)."""
    cfg, params = smoke_model
    sc = SchedulerConfig(max_slots=2, page_size=4, max_seq=32)
    eng = ServingEngine(cfg, params, mesh=mesh1(), rules=MeshRules(),
                        n_pages=32, scheduler=sc)
    reg = eng.registry
    assert all(reg._armed[h.idx] for h in eng.kv_pool.locks)
    for _ in range(3):
        eng.store.swap(params)
    assert all(reg._armed[h.idx] for h in eng.kv_pool.locks)
    assert not reg._armed[eng.store.leases.idx]    # the model lane DID flap


def test_chunked_prefill_multi_tick_equivalence(smoke_model):
    """A prompt longer than prefill_chunk spans several prefill ticks
    (each chunk attends to the already-paged prefix, nothing recomputed)
    and still matches the dense path token for token."""
    cfg, params = smoke_model
    prompts = [np.arange(1, 14, dtype=np.int32)]       # 13 > chunk of 4
    want = [dense_reference(cfg, params, prompts[0], 4)]
    sc = SchedulerConfig(max_slots=2, page_size=4, max_seq=32,
                         prefill_chunk=4, prefill_rows=1, token_budget=4)
    eng, got = run_engine(cfg, params, mesh1(), prompts, 4, sc, n_pages=32)
    assert got == want, (got, want)
    assert eng.stats.prefills >= 4                     # 13 tokens / 4-chunks


def test_eviction_under_page_pressure_preserves_output(smoke_model):
    """A pool too small for all requests forces preemption; evicted
    requests are re-prefilled (prompt + generated-so-far) and finish with
    exactly the unconstrained run's tokens."""
    cfg, params = smoke_model
    prompts = [np.arange(1, 6, dtype=np.int32) + 3 * i for i in range(3)]
    max_new = 8
    want = [dense_reference(cfg, params, p, max_new) for p in prompts]
    sc = SchedulerConfig(max_slots=3, page_size=4, max_seq=32,
                         prefill_chunk=8, prefill_rows=2, token_budget=16)
    eng, got = run_engine(cfg, params, mesh1(), prompts, max_new, sc,
                          n_pages=8)          # 3 slots want ~4 pages each
    assert got == want, (got, want)
    assert eng.scheduler.evictions >= 1, "pool was sized to force eviction"
    assert eng.kv_pool.free_count() == 8


def test_partial_admission_defers_every_unallocated_slot(smoke_model):
    """If the host free-page estimate was stale and an admitted slot's
    allocation fails, EVERY later admitted slot is un-admitted too (in
    order) — a slot left running without pages would prefill into nothing
    and stream garbage."""
    cfg, params = smoke_model
    sc = SchedulerConfig(max_slots=4, page_size=4, max_seq=32,
                         prefill_chunk=8, prefill_rows=2, token_budget=16)
    eng = ServingEngine(cfg, params, mesh=mesh1(), rules=MeshRules(),
                        n_pages=2, scheduler=sc)   # room for ONE request
    eng._free_est = 16                             # stale (too optimistic)
    slots = [SlotState(rid=i, prefix=np.arange(1, 6, dtype=np.int32),
                       max_new=2) for i in range(3)]
    for st in slots:
        eng.scheduler.submit(st)
    eng._admit()
    assert list(eng.scheduler.running.values()) == [slots[0]]
    assert slots[0].pages != []
    assert [s.rid for s in eng.scheduler.waiting] == [1, 2]  # order kept
    assert all(s.phase is Phase.WAITING and s.row == -1 and not s.pages
               for s in slots[1:])


# ---------------------------------------------------------------------------
# PageTable critical-section hygiene (the compact fix)
# ---------------------------------------------------------------------------


def test_compact_scrubs_orphans_outside_write_lock():
    env = LockEnv(LiveMem())
    pool = KVPool(32, stripes=2)
    pt = PageTable(32, env.make("bravo-ba"), pool=pool)
    pt.allocate(3, 4)
    pt.allocate(8, 2)
    lock = pt.lock
    # no orphans: compact never takes the write acquire at all (a BRAVO
    # write acquire is a bias revocation stalling every reader)
    rev_before = lock.stats.revocations
    assert pt.compact(live=[3, 8]) == 0
    assert lock.stats.revocations == rev_before
    # rid 3 dies without reclaiming -> compact frees exactly its pages
    assert pt.compact(live=[8]) == 4
    assert pool.free_count() == 30
    assert pt.lookup(8) != [] and pt.lookup(3) == []
    assert pt.compact(live=[8]) == 0                  # idempotent
    assert pt.reclaim(8) == 2


def test_compact_host_mode_still_sorts():
    env = LockEnv(LiveMem())
    pt = PageTable(16, env.make("ba"))
    pt.allocate(1, 3)
    pt.reclaim(1)
    assert pt.compact() == 0
    assert pt.free == sorted(pt.free)
