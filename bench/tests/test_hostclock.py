"""The scheduler's spans on the device trace's clock: the program's clock
anchor read back from a real profiler session, the innermost-span
attribution of idle gaps, and the three readers built on the tick spans
(``sched.step_gap_ms``, ``lease.host_ms_per_step``, ``lease.idle_share``),
on a synthetic timeline and chip."""

import copy
import glob
import os
import tempfile
import time
from types import SimpleNamespace as NS

import pytest

from bench import hostclock, spec, work
from bench.e2e import build_timeline
from bench.profile import Chip, Ev, reduce_chips

US = 1_000
OFF = 5_000_000_000          # trace clock - monotonic clock
NEW = ("sched.step_gap_ms", "lease.host_ms_per_step", "lease.idle_share")


def span(name, a, b, tick, **args):
    return NS(ts_ns=a * US, cat="engine", name=name, dur_ns=(b - a) * US,
              args=dict(args, tick=tick))


def step_tick(tick, t, kind, lease):
    """A step tick at ``t`` us: admit 10, (grow 5), lease publish
    ``lease``, dispatch, release ``lease``, read-back, finish 20."""
    out = [span("tick_admit", t, t + 10, tick)]
    s = t + 10
    if kind == "decode":
        out.append(span("tick_grow", s, s + 5, tick))
        s += 5
    end = t + 80
    out += [span(f"{kind}_step", s, end, tick, batch=2, rows=1),
            span("lease_acquire", s, s + lease, tick),
            span("step_dispatch", s + lease, s + 15, tick),
            span("lease_release", s + 15, s + 15 + lease, tick),
            span("step_readback", s + 15 + lease, end, tick),
            span("tick_finish", end, t + 100, tick),
            span("sched_tick", t, t + 100, tick)]
    return out


def timeline():
    # ticks 1-2 decode back to back, 3-4 idle, 5 prefill
    events = (step_tick(1, 0, "decode", 5) + step_tick(2, 100, "decode", 6)
              + [span("sched_idle", 200, 300, 3, polls=2)]
              + step_tick(5, 300, "prefill", 2)
              + [span("swap_land", 150, 450, None)])     # another thread
    req = [NS(ts_ns=t * US, cat="req", name=n, dur_ns=0, args=a)
           for t, n, a in (
               (0, "submit", {"rid": 1}),
               (5, "admit", {"rid": 1, "cached": 16}),
               (390, "prefill_chunk", {"rid": 1, "chunk": 24, "pos": 16}),
               (395, "first_token", {"rid": 1}),
               (900, "done", {"rid": 1, "tokens": 3}))]
    return build_timeline(events + req,
                          [NS(rid=1, at_s=0.0, prompt=[1] * 40, max_new=3)],
                          0, 1_000 * US)


def chip():
    """Device busy while the step programs run (trace clock), idle
    between them."""
    runs = [("jit_decode(7)", 27, 75), ("jit_decode(7)", 125, 178),
            ("jit_prefill(9)", 315, 375), ("jit__lambda(3)", 420, 430)]
    mods = [Ev(n, a * US + OFF, (b - a) * US, n) for n, a, b in runs]
    return Chip(ops=list(mods), modules=mods)


ANCHORS = [(OFF + m * US + lat, m * US)
           for m, lat in ((0, 3_000), (100, 0), (300, 1_000))]


def ctx(anchors, monkeypatch, chips=None):
    monkeypatch.setattr(hostclock, "_run_anchors", lambda c: anchors)
    # the traced window: [0, 1000) us, centred like the run's
    monkeypatch.setattr(hostclock, "TRACE_S", 1e-3)
    red = reduce_chips(chips or {"/device:TPU:0": chip()}, 1_000 * US)
    red.decode_ctx = [[40, 30]]
    dims = work.Dims(n_layers=1, d_model=64, n_heads=4, n_kv_heads=4,
                     head_dim=16, d_ff=128, vocab=100, glu=True)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return NS(profile=red, timeline=timeline(), dims=dims, peaks=peaks,
              chips=1)


def test_clock_offset_is_the_least_difference():
    assert hostclock.clock_offset(ANCHORS) == (OFF, 1_000)


def test_innermost_pieces_and_idle_by_span():
    pieces = hostclock.innermost([(0, 100, "tick"), (10, 40, "step"),
                                  (10, 15, "lease"), (60, 100, "finish")])
    assert pieces == [(0, 10, "tick"), (10, 15, "lease"), (15, 40, "step"),
                      (40, 60, "tick"), (60, 100, "finish")]
    got = hostclock.idle_by_span([(5, 12), (38, 45), (90, 130)], pieces)
    assert got == {"tick": 5 + 5, "lease": 2, "step": 2, "finish": 10,
                   "-": 30}
    busy = [(-5, 2), (5, 10), (20, 30), (40, 50)]
    assert hostclock.idle_intervals(busy, (0, 25)) == [(2, 5), (10, 20)]
    assert hostclock.idle_intervals(busy, (0, 60)) == [
        (2, 5), (10, 20), (30, 40), (50, 60)]


def test_readers_on_the_tick_spans(monkeypatch):
    c = ctx(ANCHORS, monkeypatch)
    read = spec.metric_reader
    # tick 2's dispatch (121 us) - tick 1's read-back end (80 us); tick 5
    # follows an idle run, so it pairs with nothing
    assert read("sched.step_gap_ms")(c) == pytest.approx(0.041)
    # decode ticks only: (5 + 5) and (6 + 6) us
    assert read("lease.host_ms_per_step")(c) == pytest.approx(0.011)
    # idle under lease spans: 5 us (tick 1, before the first op), 6 us
    # (tick 2) and 2 us (tick 5) of the 1000 us window
    assert read("lease.idle_share")(c) == pytest.approx(1.3)
    by = dict(c.profile.breakdown["idle_by_host_span"])
    assert by == pytest.approx({
        "tick_finish": 60e-6, "sched_idle": 100e-6, "tick_admit": 30e-6,
        "step_readback": 12e-6, "step_dispatch": 14e-6,
        "lease_acquire": 13e-6, "tick_grow": 10e-6, "-": 590e-6})
    # the window's idle, edges included: what device.idle_share reads
    assert sum(by.values()) == pytest.approx(1e-6 * (1000 - 171))
    assert read("device.idle_share")(c) == pytest.approx(82.9)
    h = c.profile.breakdown["host_clock"]
    assert h == {"clock_offset_ns": OFF, "anchor_jitter_ns": 1_000,
                 "anchors": 3, "decode_outside_ns": 0,
                 "device_early_ns": 0}


def test_a_wrong_offset_puts_the_decode_program_outside_its_span(
        monkeypatch):
    late = [(t + 30 * US, m) for t, m in ANCHORS]
    h = hostclock.place(ctx(late, monkeypatch))
    # spans land 30 us late: the second decode program starts 20 us
    # before its span [145, 210) us
    assert h.decode_outside_ns == 20 * US
    # and starts 26 us before its dispatch [151, 160) us began
    assert h.device_early_ns == 26 * US
    assert h.clock_offset_ns == OFF + 30 * US


def test_a_chip_ahead_of_the_host_is_moved_back(monkeypatch):
    """The profile may place a chip's ops ahead of the host plane: a
    decode program then starts before its dispatch.  The ops move later
    by the least amount that makes every decode program start after its
    dispatch began."""
    def early(us):
        c = chip()
        mods = [Ev(m.name, m.start - us * US, m.dur, m.text)
                for m in c.modules]
        return {"/device:TPU:0": Chip(ops=list(mods), modules=mods)}

    h8 = hostclock.place(ctx(ANCHORS, monkeypatch, chips=early(8)))
    # 8 us ahead: the second decode program starts at 117 us, its
    # dispatch at 121 us: moved 4 us, as a chip 4 us ahead is not moved
    assert h8.device_early_ns == 4 * US
    h4 = hostclock.place(ctx(ANCHORS, monkeypatch, chips=early(4)))
    assert h4.device_early_ns == 0
    assert h8.idle_ns == h4.idle_ns
    assert h8.decode_outside_ns == 0 and h8.idle_ns != hostclock.place(
        ctx(ANCHORS, monkeypatch)).idle_ns


def test_two_chips_average(monkeypatch):
    c = ctx(ANCHORS, monkeypatch, chips={"/device:TPU:0": chip(),
                                          "/device:TPU:1": chip()})
    assert spec.metric_reader("lease.idle_share")(c) == pytest.approx(1.3)
    by = dict(c.profile.breakdown["idle_by_host_span"])
    assert by["sched_idle"] == pytest.approx(100e-6)


@pytest.mark.parametrize("anchors", [None, ANCHORS])
def test_old_readers_and_gaps_do_not_move(monkeypatch, anchors):
    names = [m["name"] for m in spec.load_cell(
        "minicpm-2b.chat-swap").per_layer if m["name"] not in NEW]
    assert len(names) == 12
    c = ctx(anchors, monkeypatch)
    before = {n: spec.metric_reader(n)(c) for n in names}
    breakdown = copy.deepcopy(c.profile.breakdown)
    new = {n: spec.metric_reader(n)(c) for n in NEW}
    assert {n: spec.metric_reader(n)(c) for n in names} == before
    assert sum(v is not None for v in before.values()) >= 8
    for k, v in breakdown.items():
        assert c.profile.breakdown[k] == v
    if anchors is None:          # a program without anchors: no placement
        assert new["lease.idle_share"] is None
        assert c.profile.breakdown == breakdown
    else:
        assert set(c.profile.breakdown) - set(breakdown) == {
            "idle_by_host_span", "host_clock"}


def test_nothing_to_read_without_tick_spans(monkeypatch):
    monkeypatch.setattr(hostclock, "_run_anchors", lambda c: ANCHORS)
    tl = build_timeline([span("decode_step", 0, 50, None, batch=1)], [], 0,
                        1_000 * US)
    red = reduce_chips({"/device:TPU:0": chip()}, 1_000 * US)
    c = NS(profile=red, timeline=tl)
    for n in NEW:
        assert spec.metric_reader(n)(c) is None, n
    assert spec.metric_reader("lease.idle_share")(
        NS(profile=None, timeline=timeline())) is None


def test_anchor_round_trip_through_the_profiler(tmp_path, monkeypatch):
    import jax
    from jax.profiler import ProfileData
    from repro.obs import clock_anchor
    d = tmp_path / "bench-trace-x"
    jax.profiler.start_trace(str(d))
    try:
        for _ in range(50):
            clock_anchor()
        t0 = time.monotonic_ns()
        with jax.profiler.TraceAnnotation("probe"):
            time.sleep(0.01)
        t1 = time.monotonic_ns()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    prints, anchors = hostclock.read_trace(path)
    assert prints == {} and len(anchors) == 50     # no chip on the CPU
    off, jitter = hostclock.clock_offset(anchors)
    probe, = [e for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events if e.name == "probe"]
    # a span timed on the monotonic clock lands where the profiler put it
    assert abs(probe.start_ns - (t0 + off)) < 200 * US
    assert abs(probe.start_ns + probe.duration_ns - (t1 + off)) < 200 * US
    # the run's trace is found by its directory name and device planes,
    # past a newer file that does not parse
    stale = tmp_path / "bench-trace-y" / "a.xplane.pb"
    stale.parent.mkdir()
    stale.write_bytes(b"\x07not an xspace" * 64)
    later = os.path.getmtime(path) + 10
    os.utime(stale, (later, later))
    with pytest.raises(RuntimeError):
        hostclock.read_trace(str(stale))
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert hostclock._run_anchors({}) == anchors
    assert hostclock._run_anchors({"/device:TPU:0": chip()}) is None


def test_traced_cpu_run_reports_the_tick_metrics(tmp_path, monkeypatch):
    """The chat cell at the smoke widths, traced on the CPU: the two
    host-span metrics are read, the device-trace one is not (no chip)."""
    import jax
    from bench import device, run, serve
    from bench.tests import tiny
    monkeypatch.setattr(serve, "pool_pages", lambda *a: 10**6)
    cell = spec.load_cell("tiny.chat", root=tiny.make_root(tmp_path))
    res = run.measure(cell, 2**31 + 13, 3.0, True, jax.devices()[:1],
                      peaks=device.peaks("TPU v5 lite"))
    assert res["correct"], res["limits"]
    got = res["metrics"]
    assert got["sched.step_gap_ms"]["value"] > 0
    assert got["lease.host_ms_per_step"]["value"] > 0
    assert got["lease.host_ms_per_step"]["value"] < got["step.decode_ms"][
        "value"]
    assert "lease.idle_share" not in got
