"""The paged kernels walk only the lanes a row holds, and only the layer
they are given.  The decode kernel
runs a row's ``ceil(cache_len / page_size)`` live lanes; the chunk kernel
runs, per (row, q-block), the lanes up to the block's causal end, and none
for padding rows or blocks of padding columns only.  A skipped lane adds
nothing to the online softmax, so on ragged batches both kernels, bf16 and
int8, stay bit-equal to their oracles, which walk every lane; and a page
that only dead lanes name is never read: NaN there leaves the output
finite and unchanged.  The kernels take the whole lane-dense store and a
layer index: NaN in every other layer leaves the output unchanged too,
for one query head per KV head (g = 1), grouped heads (g > 1) and a
single KV head, and with KV heads that share a 128-lane tile."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.paged_attn import _paged_attn_call, _paged_attn_quant_call
from repro.kernels.paged_chunk_attn import (_chunk_attn_call,
                                            _chunk_attn_quant_call)
from repro.kernels.quant import quantize_pages

H, KVH, HD, PS, LANES, N_PAGES = 4, 2, 16, 4, 7, 48
INTERPRET = jax.default_backend() != "tpu"

# decode rows: inactive, k*ps, k*ps + 1, the full walk, one token, and a
# partial last page; rows 0, 1 and 5 also hold pages past their length
DEC_LENS = (0, 2 * PS, 2 * PS + 1, LANES * PS, 1, 5 * PS + 3)
DEC_HELD = (3, 4, 3, LANES, 1, LANES)
# chunk rows (S = 8): a padding row holding pages; a chunk of 3 (its first
# q-blocks all padding); a fresh 8-token prompt (lanes past each block's
# causal end); the full walk; k*ps + 1; one new token at k*ps
S = 8
CHUNK_ROWS = ((0, 10), (3, 13), (8, 8), (8, LANES * PS), (5, 2 * PS + 1),
              (1, 4 * PS))                    # (new_lens, cache_len)


def _pages(rng, dtype, kvh=KVH):
    k = rng.standard_normal((N_PAGES, PS, kvh, HD)).astype(np.float32)
    v = rng.standard_normal((N_PAGES, PS, kvh, HD)).astype(np.float32)
    return jnp.asarray(k, dtype), jnp.asarray(v, dtype)


def _tables(rng, held):
    """-> (page_idx with ``held[i]`` distinct pages in row i, -1 after),
    never naming page 0 (what a -1 lane clamps to)."""
    perm = rng.permutation(np.arange(1, N_PAGES))
    pi = np.full((len(held), LANES), -1, np.int32)
    off = 0
    for i, n in enumerate(held):
        pi[i, :n] = perm[off:off + n]
        off += n
    return pi


def _dead_pages(pi, live):
    """Pages that only lanes at or past each row's ``live[i]`` name, and
    page 0 unless a live lane names it."""
    named_live = {int(x) for i, n in enumerate(live) for x in pi[i, :n]}
    return sorted(({0} | {int(x) for x in pi[pi >= 0]}) - named_live)


def _decode(rng, h=H):
    q = jnp.asarray(rng.standard_normal((len(DEC_LENS), h, HD)),
                    jnp.bfloat16)
    pi = _tables(rng, DEC_HELD)
    return q, pi, np.asarray(DEC_LENS, np.int32)


def _chunk(rng, h=H):
    q = jnp.asarray(rng.standard_normal((len(CHUNK_ROWS), S, h, HD)),
                    jnp.bfloat16)
    nl = np.asarray([r[0] for r in CHUNK_ROWS], np.int32)
    cl = np.asarray([r[1] for r in CHUNK_ROWS], np.int32)
    pi = _tables(rng, [-(-c // PS) for c in cl])
    return q, pi, cl, nl


def _run_decode(kind, q, k, v, pi, cl, lps, layer=0):
    ly = jnp.int32(layer)
    if kind == "int8":
        kq, ks = k
        vq, vs = v
        out = _paged_attn_quant_call(q, kq, vq, ks, vs, ly, pi, cl,
                                     interpret=INTERPRET, lanes_per_step=lps)
        return out, jax.jit(R.paged_attn_quant_ref)(q, kq, vq, ks, vs, ly,
                                                    pi, cl)
    out = _paged_attn_call(q, k, v, ly, pi, cl, interpret=INTERPRET,
                           lanes_per_step=lps)
    return out, jax.jit(R.paged_attn_ref)(q, k, v, ly, pi, cl)


def _run_chunk(kind, q, k, v, pi, cl, nl, bq, layer=0):
    ly = jnp.int32(layer)
    ref = jax.jit(R.paged_chunk_attn_quant_ref if kind == "int8"
                  else R.paged_chunk_attn_ref, static_argnames="block_q")
    if kind == "int8":
        kq, ks = k
        vq, vs = v
        out = _chunk_attn_quant_call(q, kq, vq, ks, vs, ly, pi, cl, nl,
                                     interpret=INTERPRET, block_q=bq)
        return out, ref(q, kq, vq, ks, vs, ly, pi, cl, nl, block_q=bq)
    out = _chunk_attn_call(q, k, v, ly, pi, cl, nl, interpret=INTERPRET,
                           block_q=bq)
    return out, ref(q, k, v, ly, pi, cl, nl, block_q=bq)


def _store(kind, rng, kvh=KVH):
    """-> (k, v) as the kernel takes them, a one-layer lane-dense store:
    bf16 pages, or int8 pages with their float32 scales."""
    k, v = _pages(rng, jnp.bfloat16 if kind == "bf16" else jnp.float32, kvh)
    if kind == "int8":
        return tuple(tuple(map(R.one_layer, quantize_pages(x)))
                     for x in (k, v))
    return R.one_layer(k), R.one_layer(v)


def _poison(kind, x, dead):
    """NaN into the dead pages (their scales, for int8 pages)."""
    if kind == "int8":
        return x[0], x[1].at[:, jnp.asarray(dead)].set(jnp.nan)
    return x.at[:, jnp.asarray(dead)].set(jnp.nan)


def _deepen(kind, x, layer, depth=3):
    """A ``depth``-layer store that holds ``x``'s one layer at ``layer``
    and NaN in every other layer (NaN scales and arbitrary bytes, for
    int8 pages)."""
    if kind == "int8":
        return (jnp.concatenate([x[0]] * depth),
                jnp.full((depth,) + x[1].shape[1:], jnp.nan,
                         x[1].dtype).at[layer].set(x[1][0]))
    return jnp.full((depth,) + x.shape[1:], jnp.nan,
                    x.dtype).at[layer].set(x[0])


@pytest.mark.parametrize("lps", [1, 2, 4])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_decode_ragged_bit_equal_to_oracle(kind, lps):
    rng = np.random.default_rng(14)
    k, v = _store(kind, rng)
    q, pi, cl = _decode(rng)
    out, ref = _run_decode(kind, q, k, v, jnp.asarray(pi), jnp.asarray(cl),
                           lps)
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.array_equal(out, ref)
    assert not np.any(out[0])                 # the inactive row: zeros
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("bq", [8, 4, 2])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_chunk_ragged_bit_equal_to_oracle(kind, bq):
    rng = np.random.default_rng(15)
    k, v = _store(kind, rng)
    q, pi, cl, nl = _chunk(rng)
    out, ref = _run_chunk(kind, q, k, v, *map(jnp.asarray, (pi, cl, nl)),
                          bq)
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.array_equal(out, ref)
    assert not np.any(out[0])                 # the padding row: zeros
    assert not np.any(out[1, :S - 3])         # its padding columns: zeros
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("lps", [1, 4])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_decode_never_reads_a_dead_lane(kind, lps):
    rng = np.random.default_rng(16)
    k, v = _store(kind, rng)
    q, pi, cl = _decode(rng)
    dead = _dead_pages(pi, [-(-c // PS) for c in cl])
    assert dead
    args = (jnp.asarray(pi), jnp.asarray(cl), lps)
    clean, _ = _run_decode(kind, q, k, v, *args)
    out, _ = _run_decode(kind, q, _poison(kind, k, dead),
                         _poison(kind, v, dead), *args)
    out = np.asarray(out)
    assert np.all(np.isfinite(out))
    assert np.array_equal(out, np.asarray(clean))


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_chunk_never_reads_a_dead_lane(kind):
    rng = np.random.default_rng(17)
    k, v = _store(kind, rng)
    q, pi, cl, nl = _chunk(rng)
    # the last q-block with a real column needs the row's every page
    dead = _dead_pages(pi, [-(-c // PS) if n else 0 for n, c in
                            zip(nl, cl)])
    assert dead
    args = (*map(jnp.asarray, (pi, cl, nl)), 4)
    clean, _ = _run_chunk(kind, q, k, v, *args)
    out, _ = _run_chunk(kind, q, _poison(kind, k, dead),
                        _poison(kind, v, dead), *args)
    out = np.asarray(out)
    assert np.all(np.isfinite(out))
    assert np.array_equal(out, np.asarray(clean))


# (H, KVH): one query head per KV head, grouped heads, one KV head, and
# grouped heads whose KV heads share 128-lane tiles (8 x 16 lanes)
HEADS = {"g1": (4, 4), "g2": (4, 2), "kvh1": (4, 1), "tiled": (16, 8)}


@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_reads_only_its_layer(step, kind, layer, heads):
    """The first and the last layer of a 3-layer store, every other layer
    NaN: the output is finite, equal to the one-layer store's and bit-equal
    to the oracle's."""
    h, kvh = HEADS[heads]
    rng = np.random.default_rng(18)
    k, v = _store(kind, rng, kvh)
    deep = _deepen(kind, k, layer), _deepen(kind, v, layer)
    if step == "decode":
        q, pi, cl = _decode(rng, h)
        args = (jnp.asarray(pi), jnp.asarray(cl), 2)
        flat, _ = _run_decode(kind, q, k, v, *args)
        out, ref = _run_decode(kind, q, *deep, *args, layer=layer)
    else:
        q, pi, cl, nl = _chunk(rng, h)
        args = (*map(jnp.asarray, (pi, cl, nl)), 4)
        flat, _ = _run_chunk(kind, q, k, v, *args)
        out, ref = _run_chunk(kind, q, *deep, *args, layer=layer)
    out = np.asarray(out)
    assert np.all(np.isfinite(out))
    assert np.array_equal(out, np.asarray(flat))
    assert np.array_equal(out, np.asarray(ref))
