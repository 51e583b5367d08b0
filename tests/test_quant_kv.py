"""PR 10: int8 quantized paged-KV store (per-page scales) + quantized
paged kernels.  Covers the quantize/dequantize round trip (bit-stable,
bounded error), the quantized decode/chunk kernels against the quant
oracle (bit-exact at the default knobs) and the fp32 oracle (within the
documented quantization bound), the ``requant_scatter`` write path
(shared-prefix pages untouched byte-for-byte, stale bytes zeroed,
full-page requant bit-stable), the scale-generation freshness epoch, and
token-equivalence of the quantized engine's COW/dedup serving path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.dist.sharding import MeshRules
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.kernels.paged_attn import _paged_attn_quant_call
from repro.kernels.paged_chunk_attn import _chunk_attn_quant_call
from repro.kernels.quant import (dequantize_pages, quant_layout_tag,
                                 quantize_pages, requant_scatter)
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_pool import KVPool, page_keys
from repro.serving.scheduler import SchedulerConfig

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False


def mesh1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# Quantize / dequantize round trip
# ---------------------------------------------------------------------------


def test_round_trip_error_bound_and_saturation():
    """Per-element error <= scale/2 per (page, head) group, and the group
    absmax always lands on exactly +/-127 (the bit-stability anchor)."""
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal((16, 8, 2, 16)) * 3.0, jnp.float32)
    q, s = quantize_pages(x)
    assert q.dtype == jnp.int8 and s.shape == (16, 2)
    err = jnp.abs(dequantize_pages(q, s) - x)
    assert bool(jnp.all(err <= s[:, None, :, None] / 2 + 1e-7))
    assert bool(jnp.all(jnp.max(jnp.abs(q), axis=(-3, -1)) == 127))


def test_round_trip_bit_stable():
    """quantize(dequantize(q, s)) reproduces q AND s bit for bit — the
    property that makes quantized page hashes/dedup well defined."""
    r = np.random.default_rng(1)
    x = jnp.asarray(r.standard_normal((8, 4, 2, 8)), jnp.float32)
    q, s = quantize_pages(x)
    q2, s2 = quantize_pages(dequantize_pages(q, s))
    assert bool(jnp.array_equal(q, q2))
    assert bool(jnp.array_equal(s, s2))


def test_zero_page_quantizes_to_zero():
    """An all-zero page gets the EPS floor scale and exact-zero bytes —
    fresh pages never decode to garbage."""
    q, s = quantize_pages(jnp.zeros((2, 4, 2, 8), jnp.float32))
    assert bool(jnp.all(q == 0))
    assert bool(jnp.all(s > 0))
    assert bool(jnp.all(dequantize_pages(q, s) == 0))


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           scale_pow=st.integers(-8, 8))
    def test_round_trip_property(seed, scale_pow):
        """Across magnitudes 2^-8 .. 2^8: bounded error and bit-stable
        re-quantization."""
        r = np.random.default_rng(seed)
        x = jnp.asarray(r.standard_normal((4, 4, 2, 4)) * 2.0 ** scale_pow,
                        jnp.float32)
        q, s = quantize_pages(x)
        err = jnp.abs(dequantize_pages(q, s) - x)
        assert bool(jnp.all(err <= s[:, None, :, None] / 2 + 1e-7))
        q2, s2 = quantize_pages(dequantize_pages(q, s))
        assert bool(jnp.array_equal(q, q2))
        assert bool(jnp.array_equal(s, s2))


# ---------------------------------------------------------------------------
# Quantized kernels vs oracles
# ---------------------------------------------------------------------------

L0 = jnp.int32(0)                 # the one layer of a one-layer store


def _quant_store(x):
    """(n_pages, ps, KVH, hd) float pages -> a one-layer int8 store leaf
    (1, n_pages, ps, KVH * hd) and its scales (1, n_pages, KVH)."""
    return tuple(map(R.one_layer, quantize_pages(x)))


def _dequant_layer(q, s, kvh, layer=0):
    """One layer of an int8 store leaf back to (n_pages, ps, KVH, hd)
    float32."""
    _, n, ps, lanes = q.shape
    return dequantize_pages(q[layer].reshape(n, ps, kvh, lanes // kvh),
                            s[layer])



def _decode_case(seed, b=4, h=4, kvh=2, hd=16, n_pages=32, ps=4, lanes=6):
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


def test_decode_quant_kernel_bit_exact_vs_quant_oracle():
    """At lanes_per_step=1 the quantized decode kernel equals the quant
    oracle bit for bit (same dequant op order, both under jit)."""
    q, k, v, pi, cl = _decode_case(2)
    kq, ks = _quant_store(k)
    vq, vs = _quant_store(v)
    out = _paged_attn_quant_call(q, kq, vq, ks, vs, L0, pi, cl,
                                 interpret=jax.default_backend() != "tpu",
                                 lanes_per_step=1)
    ref = jax.jit(R.paged_attn_quant_ref)(q, kq, vq, ks, vs, L0, pi, cl)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_decode_quant_kernel_within_bound_of_fp32():
    """The quantized kernel (through the tuned public wrapper) tracks the
    fp32 oracle within the documented attention-output bound: softmax
    weights are convex, so |out - out_fp32| is bounded by the largest
    per-element V dequant error plus the score-shift term — 0.05 is the
    gated envelope at unit-variance inputs."""
    q, k, v, pi, cl = _decode_case(3)
    kq, ks = _quant_store(k)
    vq, vs = _quant_store(v)
    out = K.paged_attention_quant(q, kq, vq, ks, vs, L0, pi, cl)
    ref32 = jax.jit(R.paged_attn_ref)(q, R.one_layer(k), R.one_layer(v), L0,
                                      pi, cl)
    qref = jax.jit(R.paged_attn_quant_ref)(q, kq, vq, ks, vs, L0, pi, cl)
    # wrapper may run a tuned lanes_per_step: few-ulp vs the quant oracle
    assert np.allclose(np.asarray(out), np.asarray(qref), atol=1e-5)
    assert np.max(np.abs(np.asarray(out) - np.asarray(ref32))) < 0.05


def test_chunk_quant_kernel_bit_exact_vs_quant_oracle():
    """Default block_q: the quantized chunk-prefill kernel equals its
    oracle bit for bit, including padded rows/columns."""
    r = np.random.default_rng(4)
    b, s, h, kvh, hd, n_pages, ps, lanes = 4, 8, 4, 2, 16, 32, 4, 6
    q = jnp.asarray(r.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32)
    v = jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32)
    pi = np.full((b, lanes), -1, np.int32)
    cl = np.zeros((b,), np.int32)
    nl = np.zeros((b,), np.int32)
    perm = r.permutation(n_pages)
    off = 0
    for i in range(b - 1):                       # row b-1 stays padded
        nl[i] = int(r.integers(1, s + 1))
        cl[i] = int(r.integers(nl[i], lanes * ps + 1))
        npg = -(-cl[i] // ps)
        pi[i, :npg] = perm[off:off + npg]
        off += npg
    pi, cl, nl = map(jnp.asarray, (pi, cl, nl))
    kq, ks = _quant_store(k)
    vq, vs = _quant_store(v)
    out = _chunk_attn_quant_call(q, kq, vq, ks, vs, L0, pi, cl, nl,
                                 interpret=jax.default_backend() != "tpu",
                                 block_q=0)
    ref = jax.jit(R.paged_chunk_attn_quant_ref)(q, kq, vq, ks, vs, L0, pi,
                                                cl, nl)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert np.array_equal(np.asarray(out)[-1],
                          np.zeros_like(np.asarray(out)[-1]))
    ref32 = jax.jit(R.paged_chunk_attn_ref)(q, R.one_layer(k),
                                            R.one_layer(v), L0, pi, cl, nl)
    assert np.max(np.abs(np.asarray(out) - np.asarray(ref32))) < 0.05


# ---------------------------------------------------------------------------
# requant_scatter: the quantized write path
# ---------------------------------------------------------------------------


def test_requant_scatter_touches_only_new_token_pages():
    """Pages strictly below the first new-token lane (the shared prefix)
    keep their int8 bytes AND scales bit for bit; only the touched window
    is rewritten.  This is the byte-level COW contract."""
    r = np.random.default_rng(5)
    n_pages, ps, kvh, hd, lanes = 16, 4, 2, 8, 6
    kq, ks = _quant_store(
        jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32))
    vq, vs = _quant_store(
        jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32))
    b, s = 2, 4
    k_new = jnp.asarray(r.standard_normal((b, s, kvh, hd)), jnp.float32)
    v_new = jnp.asarray(r.standard_normal((b, s, kvh, hd)), jnp.float32)
    pages = jnp.asarray([[0, 1, 2, 3, -1, -1],
                         [4, 5, 6, 7, 8, -1]], jnp.int32)
    # row 0: tokens 8..11 of 12 -> all in lane 2; row 1: tokens 13..16 of
    # 17 -> lanes 3 and 4 (pages 7, 8)
    cache_len = jnp.asarray([12, 17], jnp.int32)
    new_lens = jnp.asarray([4, 4], jnp.int32)
    kq2, vq2, ks2, vs2 = requant_scatter(kq, vq, ks, vs, k_new, v_new,
                                         pages, cache_len, new_lens)
    touched = {2, 7, 8}                     # pages holding new tokens
    for p in range(n_pages):
        same = (bool(jnp.array_equal(kq[0, p], kq2[0, p]))
                and bool(jnp.array_equal(ks[0, p], ks2[0, p]))
                and bool(jnp.array_equal(vq[0, p], vq2[0, p]))
                and bool(jnp.array_equal(vs[0, p], vs2[0, p])))
        assert same == (p not in touched), (p, same)
    # the new rows decode back within the round-trip bound
    dk = _dequant_layer(kq2, ks2, kvh)
    for i, (c, n) in enumerate(((12, 4), (17, 4))):
        for j in range(n):
            t = c - n + j
            lane, off = t // ps, t % ps
            page = int(pages[i, lane])
            err = jnp.max(jnp.abs(dk[page, off] - k_new[i, j]))
            assert float(err) <= float(ks2[0, page].max()) / 2 + 1e-6


def test_requant_scatter_matches_explicit_requant():
    """The scatter equals quantize(dequant(old page) + new rows + zeroed
    tail) computed by hand — bitwise, including the page crossing a lane
    boundary mid-chunk."""
    r = np.random.default_rng(6)
    n_pages, ps, kvh, hd = 8, 4, 2, 8
    kq, ks = _quant_store(
        jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32))
    vq, vs = _quant_store(
        jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32))
    k_new = jnp.asarray(r.standard_normal((1, 3, kvh, hd)), jnp.float32)
    v_new = jnp.asarray(r.standard_normal((1, 3, kvh, hd)), jnp.float32)
    pages = jnp.asarray([[2, 5, 3, -1]], jnp.int32)
    cache_len = jnp.asarray([7], jnp.int32)      # tokens 4,5,6 new
    new_lens = jnp.asarray([3], jnp.int32)
    kq2, vq2, ks2, vs2 = requant_scatter(kq, vq, ks, vs, k_new, v_new,
                                         pages, cache_len, new_lens)
    # lane 1 (page 5): slots 0..2 = new tokens 4..6, slot 3 zeroed
    buf = jnp.zeros((ps, kvh, hd), jnp.float32)
    buf = buf.at[0:3].set(k_new[0])
    want_q, want_s = _quant_store(buf[None])
    assert bool(jnp.array_equal(kq2[0, 5], want_q[0, 0]))
    assert bool(jnp.array_equal(ks2[0, 5], want_s[0, 0]))
    # lane 0 (page 2, fully old): untouched — it holds no new token
    assert bool(jnp.array_equal(kq2[0, 2], kq[0, 2]))
    assert bool(jnp.array_equal(vq2[0, 2], vq[0, 2]))


def test_requant_scatter_full_page_bit_stable():
    """Re-scattering a page's own decoded rows leaves bytes and scale
    identical — repeated chunked prefill over the same page does not
    drift."""
    r = np.random.default_rng(7)
    n_pages, ps, kvh, hd = 4, 4, 2, 8
    x = jnp.asarray(r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32)
    kq, ks = _quant_store(x)
    vq, vs = _quant_store(x)
    full = _dequant_layer(kq, ks, kvh)
    pages = jnp.asarray([[1, -1]], jnp.int32)
    kq2, vq2, ks2, vs2 = requant_scatter(
        kq, vq, ks, vs, full[1][None], full[1][None], pages,
        jnp.asarray([ps], jnp.int32), jnp.asarray([ps], jnp.int32))
    assert bool(jnp.array_equal(kq2, kq))
    assert bool(jnp.array_equal(ks2, ks))


@pytest.mark.parametrize("layer", [0, 2])
def test_requant_scatter_touches_only_its_layer(layer):
    """Of a 3-layer store, only ``layer``'s touched pages change, and
    exactly as the same write into that layer alone changes them: every
    other layer keeps its bytes and scales bit for bit."""
    r = np.random.default_rng(8)
    n_pages, ps, kvh, hd = 8, 4, 2, 8

    def store():
        q, s = zip(*(_quant_store(jnp.asarray(
            r.standard_normal((n_pages, ps, kvh, hd)), jnp.float32))
            for _ in range(3)))
        return jnp.concatenate(q), jnp.concatenate(s)

    (kq, ks), (vq, vs) = store(), store()
    k_new = jnp.asarray(r.standard_normal((1, 3, kvh, hd)), jnp.float32)
    v_new = jnp.asarray(r.standard_normal((1, 3, kvh, hd)), jnp.float32)
    pages = jnp.asarray([[2, 5, 3, -1]], jnp.int32)
    cache_len, new_lens = jnp.asarray([7], jnp.int32), jnp.asarray([3])
    out = requant_scatter(kq, vq, ks, vs, k_new, v_new, pages, cache_len,
                          new_lens, jnp.int32(layer))
    alone = requant_scatter(kq[layer:layer + 1], vq[layer:layer + 1],
                            ks[layer:layer + 1], vs[layer:layer + 1], k_new,
                            v_new, pages, cache_len, new_lens, L0)
    for before, after, one in zip((kq, vq, ks, vs), out, alone):
        for other in {0, 1, 2} - {layer}:
            assert bool(jnp.array_equal(after[other], before[other]))
        assert bool(jnp.array_equal(after[layer], one[0]))
        assert not bool(jnp.array_equal(after[layer], before[layer]))


# ---------------------------------------------------------------------------
# Pool metadata: scale-generation epoch + layout-tagged keys
# ---------------------------------------------------------------------------


def test_scale_gen_bumps_on_alloc():
    """Every allocation bumps the taken pages' scale generation — the
    observable freshness epoch the checker invariant mirrors: a
    reallocated page never serves under its previous tenant's scale."""
    pool = KVPool(8, map_slots=16)
    g0 = np.asarray(pool.scale_gen)
    assert (g0 == 0).all()
    pages = pool.allocate(1, 3)
    g1 = np.asarray(pool.scale_gen)
    assert sorted(np.nonzero(g1)[0].tolist()) == sorted(pages)
    assert (g1[pages] == 1).all()
    pool.reclaim(1)
    pages2 = pool.allocate(2, 8)             # the recycled pages go again
    g2 = np.asarray(pool.scale_gen)
    assert (g2[pages] == 2).all()
    assert (g2[pages2] >= 1).all()


def test_quant_tag_changes_page_keys():
    """The quantized layout tag forks the key chain (no cross-layout
    aliasing), while tag 0 reproduces the legacy chain bit for bit."""
    toks = np.arange(1, 20, dtype=np.int32)
    kh0, kl0, ln0 = page_keys(toks, 4)
    kh0b, kl0b, _ = page_keys(toks, 4, quant_tag=0)
    assert np.array_equal(kh0, kh0b) and np.array_equal(kl0, kl0b)
    tag = quant_layout_tag(4, 2, 16)
    khq, klq, lnq = page_keys(toks, 4, quant_tag=tag)
    assert np.array_equal(ln0, lnq)          # lens describe tokens only
    assert not np.array_equal(kh0, khq)
    assert not np.array_equal(kl0, klq)
    tag2 = quant_layout_tag(4, 4, 16)        # different layout, diff chain
    khq2, _, _ = page_keys(toks, 4, quant_tag=tag2)
    assert not np.array_equal(khq, khq2)


# ---------------------------------------------------------------------------
# Quantized serving: COW/dedup token equivalence end to end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_smoke("llama3.2-1b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, params, prompts, max_new, sc, n_pages, warm=0,
           quant_kv=False):
    eng = ServingEngine(cfg, params, mesh=mesh1(), rules=MeshRules(),
                        n_pages=n_pages, scheduler=sc, quant_kv=quant_kv)
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


def test_quant_engine_cow_token_equivalence(smoke_model):
    """The quantized acceptance scenario: a warm request whose prompt
    rides shared int8 pages (+ a COW boundary page) generates EXACTLY the
    tokens of the same prompt served alone on a quantized store — dedup
    and COW on quantized pages are token-invisible.  A diverging prompt
    shares the head pages and still matches ITS quantized solo run."""
    cfg, params = smoke_model
    base = np.arange(1, 15, dtype=np.int32)
    div = base.copy()
    div[6] = 99
    max_new = 4
    sc = SchedulerConfig(max_slots=2, page_size=4, max_seq=32,
                         prefill_chunk=4, prefill_rows=2, token_budget=8)
    # solo quantized runs: fresh engine per prompt, no cache to hit
    _, solo_b = _serve(cfg, params, [base], max_new, sc, 64, quant_kv=True)
    _, solo_d = _serve(cfg, params, [div], max_new, sc, 64, quant_kv=True)
    eng, got = _serve(cfg, params, [base, base, div], max_new, sc, 64,
                      warm=1, quant_kv=True)
    assert got[0] == solo_b[0]
    assert got[1] == solo_b[0]               # warm: dedup'd int8 pages
    assert got[2] == solo_d[0]               # divergence: COW'd head
    stc = eng.lock_stats()
    assert stc["engine"]["pages_saved"] >= 3     # the warm run really hit
    assert eng.metrics.counter("pool.quant_hits").value >= 12
    assert eng.metrics.counter("pool.quant_tokens").value > 0
    # refcounts balance after drain, exactly as in the fp32 pool
    assert stc["kv_pool"]["refcount_total"] == 0
    assert stc["kv_pool"]["free"] == 64


def test_quant_engine_halves_kv_hbm(smoke_model):
    """pool.hbm_bytes: the int8 k/v leaves are exactly half their bf16
    twins; total store (scales included) stays well under."""
    cfg, params = smoke_model
    sc = SchedulerConfig(max_slots=2, page_size=4, max_seq=32,
                         prefill_chunk=4, prefill_rows=2, token_budget=8)
    kw = dict(mesh=mesh1(), rules=MeshRules(), n_pages=64, scheduler=sc)
    e_q = ServingEngine(cfg, params, quant_kv=True, **kw)
    e_f = ServingEngine(cfg, params, **kw)
    q_kv = sum(int(e_q._pages_kv[n].nbytes) for n in ("k", "v"))
    f_kv = sum(int(e_f._pages_kv[n].nbytes) for n in ("k", "v"))
    assert f_kv == 2 * q_kv
    hq = e_q.metrics.gauge("pool.hbm_bytes").value
    hf = e_f.metrics.gauge("pool.hbm_bytes").value
    assert hq < hf
    assert hq == sum(int(x.nbytes)
                     for x in jax.tree.leaves(e_q._pages_kv))
