"""The device-trace reduction on a small synthetic trace: busy time as a
union, idle share, time by kernel and program name,
the breakdown, and the per-layer readers on top of it."""

from types import SimpleNamespace as NS

import pytest

from bench import profile, spec, work
from bench.profile import Chip, Ev

US = 1_000


def chips():
    # chip 0: a decode program holding two attention kernel calls and a
    # matmul, then a publish program; chip 1: the same, with an all-reduce
    c0 = Chip(
        ops=[Ev("fusion.1", 0, 40 * US, "fusion.1 jit(decode)/dot_general"),
             Ev("custom-call.7", 40 * US, 20 * US,
                "custom-call.7 jit(decode)/jit(_paged_attn_call)/pallas"),
             Ev("custom-call.7", 60 * US, 20 * US,
                "custom-call.7 jit(decode)/jit(_paged_attn_call)/pallas"),
             Ev("fusion.2", 150 * US, 10 * US, "fusion.2 jit(_acquire)")],
        modules=[Ev("jit_decode(123)", 0, 80 * US),
                 Ev("jit__acquire_by_index_impl(45)", 150 * US, 10 * US)])
    c1 = Chip(
        ops=[Ev("all-reduce.3", 0, 30 * US, "all-reduce.3 jit(decode)"),
             Ev("fusion.1", 20 * US, 40 * US, "fusion.1 jit(decode)/dot"),
             Ev("custom-call.7", 60 * US, 20 * US,
                "custom-call.7 jit(decode)/jit(_paged_attn_call)/pallas")],
        modules=[Ev("jit_decode", 0, 80 * US)])
    return {"/device:TPU:0": c0, "/device:TPU:1": c1}


def test_union_merges_overlaps():
    n, merged = profile.union_ns([Ev("a", 0, 10), Ev("b", 5, 10),
                                  Ev("c", 30, 5)])
    assert n == 20 and merged == [(0, 15), (30, 35)]


def test_reduce_busy_idle_and_names():
    red = profile.reduce_chips(chips(), 200 * US)
    assert red.busy_ns == {"/device:TPU:0": 90 * US,
                           "/device:TPU:1": 80 * US}
    assert red.busy_s == pytest.approx(85e-6)
    assert red.window_s == pytest.approx(200e-6)
    assert red.op_ns("paged_attn") == 60 * US
    assert len(red.ops("paged_attn")) == 3
    assert red.module_ns("acquire") == 10 * US
    assert red.op_ns("all-reduce") == 30 * US
    gaps = dict(red.breakdown["idle_gaps"])
    assert gaps == {"jit_decode -> jit__acquire_by_index_impl":
                    pytest.approx(70e-6 / 2)}
    ops = dict(red.breakdown["device_ops"])
    assert ops["custom-call"] == pytest.approx(60e-6 / 2)
    assert profile._op_label(Ev(
        "%while.3 = (s32[]{:T(128)}, bf16[4,256,2304]{2,1,0}, f32[7])",
        0, 1)) == "%while bf16[4,256,2304]"
    assert len(red.breakdown["device_ops"]) <= 10


def ctx(red, n_chips):
    dims = work.Dims(n_layers=1, d_model=64, n_heads=4, n_kv_heads=4,
                     head_dim=16, d_ff=128, vocab=100, glu=True)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return NS(profile=red, dims=dims, peaks=peaks, chips=n_chips,
              timeline=None)


def test_readers_on_the_synthetic_trace():
    red = profile.reduce_chips(chips(), 200 * US)
    red.decode_ctx = [[1000, 500], [1001, 501]]
    c = ctx(red, 2)
    read = spec.metric_reader
    idle = read("device.idle_share")(c)
    assert idle == pytest.approx(100 * (1 - 170 / 400))
    assert read("lease.device_share")(c) == pytest.approx(100 * 10 / 170)
    # roofline: least time of the live K/V per call over kernel time
    least = sum(work.least_time(work.paged_attn_call(c.dims, x, 2),
                                c.peaks)[0] for x in red.decode_ctx) / 2
    got = read("kernel.paged_attn_roofline")(c)
    assert got == pytest.approx(100 * least * 3 / 60e-6)
    assert 0 < got < 100
    # nothing to read -> nothing, never 0
    assert read("kernel.chunk_attn_roofline")(c) is None
    assert read("device.idle_share")(ctx(None, 1)) is None


def test_load_finds_no_chip_in_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    assert profile.load(str(tmp_path)) == {}
    assert profile.load(str(tmp_path / "nothing")) == {}
