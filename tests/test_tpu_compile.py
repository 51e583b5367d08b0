"""Every Pallas kernel of the serving path compiles for a TPU v5e, at
minicpm-2b widths, with ``interpret=False`` — the chip's own compiler,
run here for a described (not attached) chip.  Interpret-mode tests check
what the kernels compute; these check that Mosaic accepts them: tiling,
scalar placement, layouts, compile time.  The whole paged decode and
prefill steps compile too, and their compiled HLO moves no store-sized
buffer: the layer scan carries the donated store and writes it in place.

The topology is described inside a fixture (never at import, in
``parametrize`` or in ``skipif``): only one process may load the TPU
library, and every test worker imports this file."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attn import _paged_attn_call, _paged_attn_quant_call
from repro.kernels.paged_chunk_attn import (_chunk_attn_call,
                                            _chunk_attn_quant_call)
from repro.kernels.table_publish import (_fused_publish_call,
                                         _fused_publish_multi_call)
from repro.kernels.table_scan import (_multi_poll_call, _poll_call,
                                      _scan_call)

# minicpm-2b: 36 heads (MHA), head dim 64; the engine's page geometry
B, H, KVH, HD, PS, N_PAGES, LANES = 8, 36, 36, 64, 16, 512, 64
N_LAYERS = 4                           # depth of the store the kernels get
ROWS, CHUNK = 4, 128                   # chunked-prefill batch
TABLE, M, LOCKS = (32, 128), 8, 128    # lease table, batch, registry lanes


def _cases(s):
    """name -> (callable, abstract args); ``s(shape, dtype)`` places a
    shape on the described chip."""
    i32, bf16, i8, f32 = jnp.int32, jnp.bfloat16, jnp.int8, jnp.float32
    pages = (N_LAYERS, N_PAGES, PS, KVH * HD)
    dec = (s((B, H, HD), bf16),)
    chunk = (s((ROWS, CHUNK, H, HD), bf16),)
    scales = (s((N_LAYERS, N_PAGES, KVH), f32),) * 2
    layer = (s((), i32),)
    dec_rows = layer + (s((B, LANES), i32), s((B,), i32))
    chunk_rows = layer + (s((ROWS, LANES), i32), s((ROWS,), i32),
                          s((ROWS,), i32))
    table = s(TABLE, i32)
    return {
        "paged_attn": (_paged_attn_call, dec + (s(pages, bf16),) * 2
                       + dec_rows),
        "paged_attn_quant": (_paged_attn_quant_call, dec
                             + (s(pages, i8),) * 2 + scales + dec_rows),
        "chunk_attn": (_chunk_attn_call, chunk + (s(pages, bf16),) * 2
                       + chunk_rows),
        "chunk_attn_quant": (_chunk_attn_quant_call, chunk
                             + (s(pages, i8),) * 2 + scales + chunk_rows),
        "fused_publish": (_fused_publish_call, (
            table, s((), i32), s((M,), i32), s((M,), i32))),
        "fused_clear": (lambda *a, **kw: _fused_publish_call(
            *a, unconditional=True, check_rbias=False, **kw), (
            table, s((), i32), s((M,), i32), s((M,), i32))),
        "fused_publish_multi": (_fused_publish_multi_call, (
            table, s((LOCKS,), i32), s((M,), i32), s((M,), i32),
            s((M,), i32))),
        "scan": (_scan_call, (table, s((), i32))),
        "poll": (_poll_call, (table, s((), i32))),
        "multi_poll": (_multi_poll_call, (table, s((5,), i32))),
    }


KERNELS = ("paged_attn", "paged_attn_quant", "chunk_attn",
           "chunk_attn_quant", "fused_publish", "fused_clear",
           "fused_publish_multi", "scan", "poll", "multi_poll")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def on_chip(topo):
    """-> shape placer for one described chip; the persistent compile
    cache is off meanwhile (a chip-less compile can be written to it but
    never read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                        sharding=sharding)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, on_chip):
    fn, args = _cases(on_chip)[kernel]
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# Whole paged steps: the store is carried through the layer scan and
# written in place
# ---------------------------------------------------------------------------

STEP_PAGES, STEP_LANES, SLOTS = 780, 192, 16     # the chat cell's pool
INSTR = re.compile(r"^\s*%?\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\((.*)$",
                   re.M)


def _store_movers(hlo: str, n_pages: int, layer_elems: int):
    """-> (opcode, dims) of every instruction that yields an array with
    the store's page extent (``n_pages`` as a dim, at least a layer's
    elements) and is not the in-place scatter or the kernel call: a copy,
    slice, update-slice, transpose or fusion of the store.  Tuples,
    parameters, tuple elements and bitcasts move no bytes."""
    out = []
    for m in INSTR.finditer(hlo):
        dims = [int(d) for d in m.group(2).split(",") if d]
        op = m.group(3)
        if n_pages not in dims or np.prod(dims) < layer_elems:
            continue
        if op in ("parameter", "get-tuple-element", "bitcast", "scatter",
                  "custom-call"):
            continue
        if op == "fusion" and "/scatter" in m.group(4):
            continue
        out.append((op, tuple(dims)))
    return out


@pytest.fixture(scope="module")
def steps_on_chip(topo):
    """-> {(step, kind): compiled} for the paged decode and prefill steps
    at minicpm-2b widths, ``N_LAYERS`` deep, over a 780-page store that
    the step donates, compiled for one described v5e with the kernels in
    Mosaic (the CPU backend would pick interpret mode)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.kernels import ops as K
    from repro.models import model as M
    from repro.serving.steps import (make_decode_step,
                                     make_paged_prefill_step)

    base, rules, _ = configs.get("minicpm-2b")
    cfg = dataclasses.replace(base, n_layers=N_LAYERS)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=rep), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    params = placed(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    was_interpret = K._interpret
    was_cache = jax.config.jax_enable_compilation_cache
    K._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        out = {}
        for kind in ("bf16", "int8"):
            store = placed(jax.eval_shape(lambda: M.init_paged_caches(
                cfg, STEP_PAGES, PS, quantized=kind == "int8")))
            cases = {
                "decode": (make_decode_step(cfg, mesh, rules, paged=True),
                           (i32(SLOTS, 1), i32(SLOTS),
                            i32(SLOTS, STEP_LANES))),
                "prefill": (make_paged_prefill_step(cfg, mesh, rules),
                            (i32(ROWS, 256), i32(ROWS), i32(ROWS),
                             i32(ROWS, STEP_LANES))),
            }
            for step, (fn, rows) in cases.items():
                out[step, kind] = (jax.jit(fn, donate_argnums=(1,)).lower(
                    params, store, *rows).compile(), store)
        yield out
    finally:
        K._interpret = was_interpret
        jax.config.update("jax_enable_compilation_cache", was_cache)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_paged_step_writes_store_in_place(steps_on_chip, step, kind):
    """No store-sized copy, slice or update-slice: the store's bytes alias
    the output and the temporaries are a sliver of the store.  (The int8
    store's temporaries also hold the float32 requant window of the pages
    a chunk touches, ``kernels.quant.requant_scatter``: less than one
    layer of the int8 store, whatever the depth.)"""
    compiled, store = steps_on_chip[step, kind]
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    layer_elems = STEP_PAGES * PS * KVH * HD
    assert _store_movers(hlo, STEP_PAGES, layer_elems) == []
    mem = compiled.memory_analysis()
    store_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(store))
    if kind == "bf16":
        assert mem.temp_size_in_bytes < store_bytes / 100
    else:
        assert mem.temp_size_in_bytes < layer_elems
    assert mem.alias_size_in_bytes >= store_bytes


def test_store_movers_sees_a_relayout():
    """The check finds what the parent's steps did to the store: a
    transposed copy and a dynamic slice of a layer."""
    hlo = """
  %copy.1 = bf16[1,780,16,36,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%x)
  %ds = bf16[1,780,16,2304]{3,2,1,0} dynamic-slice(%p, %i)
  %s = bf16[4,780,16,2304]{3,2,1,0} fusion(%a, %b), kind=kCustom, metadata={op_name="jit(decode)/while/body/scatter"}
  %g = bf16[4,780,16,2304]{3,2,1,0} get-tuple-element(%w), index=2
  %t = s32[780]{0} copy(%o)
"""
    assert _store_movers(hlo, 780, 780 * 16 * 2304) == [
        ("copy", (1, 780, 16, 36, 64)), ("dynamic-slice", (1, 780, 16, 2304))]
