"""Tier-1 tests for the SLO plane (PR 9): windowed percentile monitors,
the latency-feedback admission controller, the trace-driven load
generator, and the attainment report fold.

Pure-host tests — ``repro.obs`` is stdlib-only and the scheduler /
controller are pure policy FSMs (numpy, no jax), so everything here
runs without a device."""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from repro.obs import chrome
from repro.obs.metrics import MetricsRegistry
from repro.obs.slo import (SLOReport, SLOTarget, WindowedHistogram,
                           _percentile)
from repro.obs.trace import Tracer, derive_requests
from repro.serving.loadgen import (LoadgenConfig, TenantClass,
                                   generate_trace)
from repro.serving.scheduler import (ControllerConfig,
                                     LatencyFeedbackController, Phase,
                                     Scheduler, SchedulerConfig, SlotState)

S = 1_000_000_000          # 1 second in ns


# ---------------------------------------------------------------------------
# windowed histogram: rotation, expiry, merge, accuracy
# ---------------------------------------------------------------------------


def test_window_counts_and_rotation():
    w = WindowedHistogram("t", window_s=1.0, slices=4)
    for i in range(100):
        w.observe(500, now_ns=i * 10_000_000)        # 10 ms apart: 1 s span
    assert w.count(now_ns=99 * 10_000_000) == 100
    # half the samples fall out once the clock advances half a window
    # past the last sample (slice granularity: allow one slice of slack)
    mid = w.count(now_ns=99 * 10_000_000 + S // 2)
    assert 25 <= mid <= 75
    # ... and all of them once it advances several windows
    assert w.count(now_ns=99 * 10_000_000 + 5 * S) == 0
    assert w.quantile(0.99, now_ns=99 * 10_000_000 + 5 * S) == 0.0


def test_window_slot_reuse_rezeros_stale_periods():
    w = WindowedHistogram("t", window_s=1.0, slices=4)
    w.observe(100, now_ns=0)
    # ring has slices+1 = 5 slots; period 5 reuses period 0's slot
    w.observe(900, now_ns=5 * (S // 4))
    assert w.count(now_ns=5 * (S // 4)) == 1
    assert w.mean(now_ns=5 * (S // 4)) == 900.0


def test_window_merge_is_deterministic_across_threads():
    w = WindowedHistogram("t", window_s=2.0, slices=8)
    ref = WindowedHistogram("ref", window_s=2.0, slices=8)
    rng = np.random.default_rng(0)
    samples = [(int(v), int(t)) for v, t in
               zip(rng.integers(100, 10_000, 400),
                   np.sort(rng.integers(0, int(1.5 * S), 400)))]
    for v, t in samples:
        ref.observe(v, now_ns=t)

    def worker(part):
        for v, t in part:
            w.observe(v, now_ns=t)

    threads = [threading.Thread(target=worker, args=(samples[i::4],))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    now = int(1.5 * S)
    assert w.count(now) == ref.count(now) == 400
    for q in (0.5, 0.9, 0.99):
        assert w.quantile(q, now) == ref.quantile(q, now)
    assert w.window_snapshot(now) == ref.window_snapshot(now)


def test_window_quantile_tracks_drifting_distribution():
    """p50/p99 over the last window match numpy on exactly the samples
    still in the window, within the log-bucket ±12.5% contract — the
    monitor must follow a drift (old, slower samples expire)."""
    w = WindowedHistogram("t", window_s=1.0, slices=8)
    rng = np.random.default_rng(7)
    t, dt = 0, 2_000_000                   # 2 ms between samples
    history = []
    for phase_scale in (1_000.0, 10_000.0, 3_000.0):
        for _ in range(500):
            v = float(rng.lognormal(np.log(phase_scale), 0.3))
            w.observe(v, now_ns=t)
            history.append((t, v))
            t += dt
    # compare against exactly the samples the window still covers
    # (slices [cur - slices, cur], mirroring the merge)
    cur = t // w.slice_ns
    in_window = [v for ts, v in history
                 if ts // w.slice_ns >= cur - w.slices]
    for q in (0.50, 0.99):
        got = w.quantile(q, now_ns=t)
        want = float(np.percentile(in_window, q * 100))
        assert got == pytest.approx(want, rel=0.13), q
    # a full-history histogram would sit near 3000/10000 mixture —
    # check the monitor forgot the 10x phase
    assert w.quantile(0.50, now_ns=t) < 5_000.0


def test_registry_windowed_and_snapshot():
    m = MetricsRegistry()
    w = m.windowed("slo.step_ns", window_s=1.0, slices=4)
    assert m.windowed("slo.step_ns") is w
    with pytest.raises(TypeError):
        m.histogram("slo.step_ns")
    w.observe(1234)                          # real clock: still in window
    snap = m.snapshot()
    assert snap["slo.step_ns"]["count"] == 1
    assert snap["slo.step_ns"]["window_s"] == 1.0


def test_percentile_matches_numpy():
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for q in (0.0, 0.25, 0.5, 0.75, 0.99, 1.0):
        assert _percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q * 100)))


# ---------------------------------------------------------------------------
# latency-feedback controller
# ---------------------------------------------------------------------------


def _cc(**kw):
    base = dict(step_p99_target_ms=10.0, period_s=0.05, window_s=1.0,
                min_samples=1, min_slots=1, decrease=0.5,
                recover_after=2, cooldown=2, probe_after=8,
                watermark_step=0.05, watermark_max=0.5)
    base.update(kw)
    return ControllerConfig(**base)


def test_controller_shrinks_past_knee_and_recovers():
    ctrl = LatencyFeedbackController(_cc(), max_slots=8)
    # over target: multiplicative decrease + watermark raise
    assert ctrl.step(20e6, 10, 0, 0) == "shrink"
    assert ctrl.slot_cap == 4 and ctrl.free_frac == pytest.approx(0.05)
    assert ctrl.ceiling == 7
    # hysteresis: cooldown swallows the next `cooldown` updates
    assert ctrl.step(20e6, 10, 0, 0) is None
    assert ctrl.step(1e6, 10, 0, 0) is None
    # additive recovery after `recover_after` healthy updates
    assert ctrl.step(1e6, 10, 0, 0) is None
    assert ctrl.step(1e6, 10, 0, 0) == "grow"
    assert ctrl.slot_cap == 5 and ctrl.free_frac == pytest.approx(0.0)


def test_controller_never_wedges_at_min():
    """Wedge-freedom: the cap can never leave [min_slots, max_slots] and
    the watermark never reaches 1.0, however hostile the sensor."""
    ctrl = LatencyFeedbackController(_cc(cooldown=0), max_slots=8)
    for _ in range(50):
        ctrl.step(1e9, 10, 1e9, 10)
        assert 1 <= ctrl.slot_cap <= 8
        assert 0.0 <= ctrl.free_frac <= 0.5
    assert ctrl.slot_cap == 1
    # ... and sustained health probes the ceiling back up from the floor
    grows = 0
    for _ in range(200):
        grows += ctrl.step(1e6, 10, 0, 0) == "grow"
    assert ctrl.slot_cap == 8 and grows >= 7


def test_controller_min_samples_and_disabled_sensors():
    ctrl = LatencyFeedbackController(_cc(min_samples=3), max_slots=8)
    assert ctrl.step(20e6, 2, 0, 0) is None          # too few samples
    assert ctrl.slot_cap == 8
    off = LatencyFeedbackController(
        _cc(step_p99_target_ms=0.0), max_slots=8)
    assert off.step(1e12, 100, 1e12, 100) is None    # both sensors off
    assert off.slot_cap == 8


def test_controller_converges_near_knee_without_oscillation():
    """Synthetic knee: latency is healthy at <= 5 active slots and 2x
    the target above.  The loop must settle near the knee and stop
    flapping (bounded decisions in the late phase)."""
    knee = 5
    ctrl = LatencyFeedbackController(_cc(probe_after=50), max_slots=16)
    decisions = []
    for i in range(600):
        lat = 5e6 if ctrl.slot_cap <= knee else 20e6
        decisions.append(ctrl.step(lat, 10, 0, 0))
    late = decisions[300:]
    caps_late = []
    cap = ctrl.slot_cap
    # replay: track the cap trajectory over the late phase
    ctrl2 = LatencyFeedbackController(_cc(probe_after=50), max_slots=16)
    for i in range(600):
        lat = 5e6 if ctrl2.slot_cap <= knee else 20e6
        ctrl2.step(lat, 10, 0, 0)
        if i >= 300:
            caps_late.append(ctrl2.slot_cap)
    assert max(caps_late) <= knee + 1          # never far past the knee
    assert min(caps_late) >= 2                 # never collapses to floor
    # hysteresis: the late phase is mostly steady state — a decision at
    # most every ~12 updates (one bounded probe cycle per probe_after)
    changes = sum(1 for d in late if d is not None)
    assert changes <= len(late) // 12


def test_controller_windowed_update_reads_sensors():
    reg = MetricsRegistry()
    w = reg.windowed("slo.step_ns", window_s=1.0, slices=4)
    ctrl = LatencyFeedbackController(_cc(cooldown=0), max_slots=8,
                                     step_window=w)
    for i in range(10):
        w.observe(50e6, now_ns=i * 10_000_000)
    assert ctrl.update(now_ns=100_000_000) == "shrink"
    assert ctrl.last_step_p99_ns > 10e6
    # window expires -> no samples -> no decision either way
    before = ctrl.slot_cap
    assert ctrl.update(now_ns=100_000_000 + 10 * S) is None
    assert ctrl.slot_cap == before


# ---------------------------------------------------------------------------
# scheduler: priority admission, aging, runtime limits
# ---------------------------------------------------------------------------


def _slot(rid, priority=0, n=8):
    return SlotState(rid=rid, prefix=np.arange(1, n + 1, dtype=np.int32),
                     max_new=4, priority=priority)


def test_admission_prefers_priority_then_arrival():
    sched = Scheduler(SchedulerConfig(max_slots=2, page_size=8,
                                      max_seq=32, aging_every=0), 64)
    for rid, pri in ((0, 0), (1, 1), (2, 1), (3, 0)):
        sched.submit(_slot(rid, pri))
    admitted = sched.admit(64)
    assert [st.rid for st in admitted] == [1, 2]     # both slots: pri 1


def test_aging_admission_is_starvation_free():
    sched = Scheduler(SchedulerConfig(max_slots=1, page_size=8,
                                      max_seq=32, aging_every=2), 64)
    sched.submit(_slot(0, priority=0))               # old, low priority
    for rid in range(1, 8):
        sched.submit(_slot(rid, priority=5))
    order = []
    while sched.waiting:
        st = sched.admit(64)[0]
        order.append(st.rid)
        sched.finish(st)
    # every aging_every-th admission takes the oldest: rid 0 lands second
    assert order[1] == 0
    assert set(order) == set(range(8))


def test_set_limits_clamps_and_caps_admission():
    sched = Scheduler(SchedulerConfig(max_slots=4, page_size=8,
                                      max_seq=32), 64)
    sched.set_limits(slot_cap=0, free_frac=2.0)      # hostile values
    assert sched.slot_cap == 1 and sched.admit_free_frac == 0.95
    sched.set_limits(slot_cap=99, free_frac=-1.0)
    assert sched.slot_cap == 4 and sched.admit_free_frac == 0.0
    sched.set_limits(slot_cap=2)
    for rid in range(4):
        sched.submit(_slot(rid))
    assert len(sched.admit(64)) == 2                 # cap, not max_slots
    assert sched.stats()["slot_cap"] == 2


# ---------------------------------------------------------------------------
# load generator
# ---------------------------------------------------------------------------


def _lg(**kw):
    base = dict(duration_s=6.0, base_rps=8.0, seed=3)
    base.update(kw)
    return LoadgenConfig(**base)


def test_loadgen_is_deterministic():
    a, b = generate_trace(_lg()), generate_trace(_lg())
    assert len(a.requests) == len(b.requests) > 10
    for x, y in zip(a.requests, b.requests):
        assert x.at_s == y.at_s and x.rid == y.rid
        assert np.array_equal(x.prompt, y.prompt)
    c = generate_trace(_lg(seed=4))
    assert [r.at_s for r in c.requests] != [r.at_s for r in a.requests]


def test_loadgen_bursts_and_zipf_sharing():
    cfg = _lg(duration_s=20.0, burst_factor=6.0, burst_period_s=4.0,
              burst_duty=0.25)
    tr = generate_trace(cfg)
    in_burst = sum(1 for r in tr.requests
                   if (r.at_s % cfg.burst_period_s) / cfg.burst_period_s
                   < cfg.burst_duty)
    # 25% of the time carries 6x the rate -> expect the majority of
    # arrivals inside bursts (2/3 in expectation)
    assert in_burst > len(tr.requests) * 0.45
    counts = np.bincount([r.sys_id for r in tr.requests],
                         minlength=cfg.n_system_prompts)
    assert counts[0] == max(counts) and counts[0] > len(tr.requests) / 4
    # shared prefix is byte-identical across requests of the same rank
    r0 = [r for r in tr.requests if r.sys_id == 0]
    assert np.array_equal(r0[0].prompt[:cfg.system_prompt_len],
                          r0[1].prompt[:cfg.system_prompt_len])


def test_loadgen_respects_engine_budget():
    cfg = _lg(duration_s=10.0, suffix_len_median=40.0,
              max_new_median=40.0, max_seq=64)
    for r in generate_trace(cfg).requests:
        assert len(r.prompt) + r.max_new <= cfg.max_seq
        assert r.max_new >= 1


# ---------------------------------------------------------------------------
# report fold: preemptions, attainment, pool counters
# ---------------------------------------------------------------------------


def test_derive_requests_preemption_keeps_first_admission():
    tr = Tracer(capacity=256)
    tr.enable()
    tr.emit("req", "submit", rid=1)
    tr.emit("req", "admit", rid=1)
    tr.emit("req", "evict", rid=1)               # preempted before TTFT
    tr.emit("req", "admit", rid=1)               # requeue re-admission
    tr.emit("req", "first_token", rid=1)
    tr.emit("req", "done", rid=1, tokens=4)
    r = derive_requests(tr.snapshot())[1]
    assert r["preemptions"] == 1 and r["evictions"] == 1
    evs = tr.snapshot()
    first_admit = next(e for e in evs if e.name == "admit")
    submit = next(e for e in evs if e.name == "submit")
    assert r["admit_ts"] == first_admit.ts_ns    # FIRST admit, not requeue
    assert r["ttft_ns"] == r["first_token_ts"] - submit.ts_ns
    assert r["queue_ns"] == first_admit.ts_ns - submit.ts_ns


def test_derive_requests_ttft_counts_the_queue_wait():
    tr = Tracer(capacity=256)
    tr.enable()
    tr.emit("req", "submit", rid=7)
    time.sleep(0.02)                             # waits for a slot
    tr.emit("req", "admit", rid=7)
    tr.emit("req", "first_token", rid=7)
    tr.emit("req", "done", rid=7, tokens=2)
    tr.emit("req", "admit", rid=8)               # never submitted: no TTFT
    tr.emit("req", "first_token", rid=8)
    reqs = derive_requests(tr.snapshot())
    r = reqs[7]
    assert r["queue_ns"] >= 20_000_000
    assert r["ttft_ns"] == r["first_token_ts"] - r["submit_ts"]
    assert r["ttft_ns"] == r["queue_ns"] + (r["first_token_ts"]
                                            - r["admit_ts"])
    assert reqs[8]["ttft_ns"] is None and reqs[8]["queue_ns"] is None


def test_slo_report_attainment_fold():
    reqs = {
        1: {"ttft_ns": 100e6, "tpot_ns": 10e6, "done_ts": 1,
            "preemptions": 0},
        2: {"ttft_ns": 900e6, "tpot_ns": 10e6, "done_ts": 1,
            "preemptions": 2},
        3: {"ttft_ns": 50e6, "tpot_ns": 10e6, "done_ts": 1,
            "preemptions": 0},
    }
    classes = {1: ("a", "interactive"), 2: ("a", "interactive"),
               3: ("b", "batch")}
    targets = {"interactive": SLOTarget("interactive", ttft_ms=500.0),
               "batch": SLOTarget("batch")}
    rep = SLOReport.from_requests(
        reqs, classes=classes, targets=targets,
        pool_stats={"prefix_lookups": 10, "prefix_hits": 6,
                    "prefix_collisions": 2}, pages_saved=12)
    assert rep.per_class["interactive"]["attainment"] == 0.5
    assert rep.per_class["batch"]["attainment"] == 1.0
    assert rep.overall["attained"] == 2
    assert rep.overall["attainment"] == pytest.approx(2 / 3, abs=1e-3)
    assert rep.overall["preemptions"] == 2
    assert rep.pool["collision_rate"] == pytest.approx(0.2)
    assert rep.pool["pages_saved"] == 12
    d = json.loads(json.dumps(rep.to_dict()))    # JSON-clean
    assert d["per_tenant"]["b"]["requests"] == 1


def test_slo_target_missing_ttft_counts_as_miss():
    t = SLOTarget("x", ttft_ms=100.0)
    assert not t.met(None, None)                 # enabled clause, no data
    assert t.met(50e6, None)
    assert SLOTarget("y").met(None, None)        # all clauses disabled


# ---------------------------------------------------------------------------
# chrome counter tracks
# ---------------------------------------------------------------------------


def test_chrome_counter_track_round_trip():
    tr = Tracer(capacity=64)
    tr.enable()
    tr.emit("sched", "ctrl_state", watermark_pct=5.0, slot_cap=4,
            active_slots=3, p99_step_us=900.0, note="dropped")
    tr.emit("sched", "ctrl_shrink", cap=4, watermark_pct=5.0)
    out = chrome.to_chrome(tr.snapshot())
    counters = [r for r in out["traceEvents"] if r.get("ph") == "C"]
    assert len(counters) == 1
    c = counters[0]
    assert c["name"] == "sched.ctrl_state" and c["tid"] == 0
    assert c["args"] == {"watermark_pct": 5.0, "slot_cap": 4,
                         "active_slots": 3, "p99_step_us": 900.0}
    assert chrome.validate(out) == []
    assert json.loads(json.dumps(out)) == out
    # the decision event stays an instant, not a counter sample
    assert any(r["ph"] == "i" and r["name"] == "sched.ctrl_shrink"
               for r in out["traceEvents"])


def test_chrome_validate_rejects_malformed_counter():
    base = {"displayTimeUnit": "ms", "traceEvents": [
        {"name": "sched.ctrl_state", "cat": "sched", "ph": "C",
         "ts": 1.0, "pid": 1, "tid": 0, "args": {}}]}
    assert chrome.validate(base)                 # empty args: invalid
    base["traceEvents"][0]["args"] = {"cap": "four"}
    assert chrome.validate(base)                 # non-numeric: invalid
    base["traceEvents"][0]["args"] = {"cap": 4}
    assert chrome.validate(base) == []
