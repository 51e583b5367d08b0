"""Every Pallas kernel of the serving path compiles for a TPU v5e, at
minicpm-2b widths, with ``interpret=False`` — the chip's own compiler,
run here for a described (not attached) chip.  Interpret-mode tests check
what the kernels compute; these check that Mosaic accepts them: tiling,
scalar placement, layouts, compile time.

The topology is described inside a fixture (never at import, in
``parametrize`` or in ``skipif``): only one process may load the TPU
library, and every test worker imports this file."""

import jax
import jax.numpy as jnp
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
ROWS, CHUNK = 4, 128                   # chunked-prefill batch
TABLE, M, LOCKS = (32, 128), 8, 128    # lease table, batch, registry lanes


def _cases(s):
    """name -> (callable, abstract args); ``s(shape, dtype)`` places a
    shape on the described chip."""
    i32, bf16, i8, f32 = jnp.int32, jnp.bfloat16, jnp.int8, jnp.float32
    pages = (N_PAGES, PS, KVH, HD)
    dec = (s((B, H, HD), bf16),)
    chunk = (s((ROWS, CHUNK, H, HD), bf16),)
    scales = (s((N_PAGES, KVH), f32), s((N_PAGES, KVH), f32))
    dec_rows = (s((B, LANES), i32), s((B,), i32))
    chunk_rows = (s((ROWS, LANES), i32), s((ROWS,), i32), s((ROWS,), i32))
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
