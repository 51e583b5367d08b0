"""A whole run of the chat cell, cut to the smoke widths, driven on the CPU
past the harness's look for a chip: the schedule is served open-loop,
nothing compiles inside the window, the trace loses nothing, and the
served tokens agree with the float32 reference."""

import jax
import pytest

from bench import device, run, serve, spec
from bench.tests import tiny


@pytest.fixture()
def cell(tmp_path, monkeypatch):
    # the CPU backend reports no HBM: give the pool a fixed size
    monkeypatch.setattr(serve, "pool_pages", lambda *a: 10**6)
    return spec.load_cell("tiny.chat", root=tiny.make_root(tmp_path))


@pytest.mark.parametrize("trace", [False, True])
def test_run_is_correct(cell, trace):
    res = run.measure(cell, 2**31 + 11, 3.0, trace, jax.devices()[:1],
                      peaks=device.peaks("TPU v5 lite"))
    assert res["correct"], res["limits"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "limits"
    lim = res["limits"]
    assert lim["window_compiles"]["value"] == 0
    assert lim["trace_events_dropped"]["value"] == 0
    if trace:
        got = set(res["metrics"])
        # the host-span metrics exist on any backend; device-trace ones
        # need a TPU plane and are left out here
        assert {"sched.queue_wait_p50_ms", "sched.decode_rows_mean",
                "pool.prefix_hit_share", "step.decode_ms",
                "step.prefill_ms", "model.mfu.decode"} <= got
        assert "device.idle_share" not in got
        assert 0 < res["metrics"]["pool.prefix_hit_share"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {"ttft_p50_ms", "tpot_p50_ms",
                                       "output_tok_s", "setup_s"}
        assert all(v["value"] > 0 for v in res["metrics"].values())
