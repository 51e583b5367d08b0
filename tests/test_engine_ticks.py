"""The scheduler loop's spans: every phase of a step tick nests inside
its ``sched_tick`` (and the lease, dispatch and read-back phases inside
the step span) under the same ``tick``; a run of idle iterations is one
``sched_idle`` span with no phase spans; and each step tick and each idle
run puts one clock anchor on the profiler's timeline.  Each decode step
carries the decode kernel's grid and the lanes of it the rows' lengths
make live."""

from __future__ import annotations

import collections
import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.dist.sharding import MeshRules
from repro.models import model as M
from repro.obs import CLOCK_ANCHOR, TRACER
from repro.serving.engine import EngineConfig, Request, ServingEngine
from repro.serving.scheduler import SchedulerConfig

PHASES = ("tick_compact", "tick_admit", "tick_grow", "lease_acquire",
          "step_dispatch", "lease_release", "step_readback", "tick_finish")
IN_STEP = ("lease_acquire", "step_dispatch", "lease_release",
           "step_readback")
IDLE_POLL_S = 0.02
SC = SchedulerConfig(max_slots=4, page_size=8, max_seq=160,
                     prefill_chunk=16, prefill_rows=2, token_budget=32)
MAX_NEW = 4


def _prompt_len(rid: int) -> int:
    return 12 + 9 * rid


def _serve_traced(trace_dir: str):
    cfg = configs.get_smoke("minicpm-2b")      # the benchmark's CPU widths
    sc = SC
    eng = ServingEngine(
        cfg, M.init_params(jax.random.PRNGKey(0), cfg),
        mesh=Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                  ("data", "model")),
        rules=MeshRules(), n_pages=96, scheduler=sc,
        engine_cfg=EngineConfig(idle_poll_s=IDLE_POLL_S))
    rng = np.random.default_rng(0)

    def serve(rids, compact=False):
        reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab,
                                                   _prompt_len(i))
                        .astype(np.int32), max_new=MAX_NEW) for i in rids]
        for r in reqs:
            eng.submit(r)
        if compact:                 # serviced by a tick of these requests
            eng.request_compaction()
        for r in reqs:
            assert r.done.wait(timeout=300), "request timed out"

    TRACER.clear()
    TRACER.enable()
    jax.profiler.start_trace(trace_dir)
    try:
        eng.start()
        serve(range(3))
        serve(range(3, 5), compact=True)
        time.sleep(12 * IDLE_POLL_S)            # an idle stretch to end on
        eng.stop()
    finally:
        jax.profiler.stop_trace()
        TRACER.disable()
    return [e for e in TRACER.snapshot() if e.cat in ("engine", "req")]


def _anchors(trace_dir: str) -> int:
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return sum(1 for p in ProfileData.from_file(path).planes
               for line in p.lines for e in line.events
               if e.name == CLOCK_ANCHOR)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """-> (the run's engine and req events, its profiler trace dir)."""
    trace_dir = str(tmp_path_factory.mktemp("ticks"))
    return _serve_traced(trace_dir), trace_dir


def test_tick_phases_nest_and_an_idle_run_is_one_span(traced):
    events, trace_dir = traced
    spans = collections.defaultdict(list)
    for e in events:
        if e.cat == "engine" and e.dur_ns:
            spans[e.name].append(e)

    def by_tick(names):
        out = {}
        for n in names:
            for e in spans[n]:
                assert e.args["tick"] not in out, (n, e.args)
                out[e.args["tick"]] = e
        return out

    ticks = by_tick(["sched_tick"])
    steps = by_tick(["decode_step", "prefill_step"])
    assert spans["decode_step"] and spans["prefill_step"]
    assert spans["tick_compact"], "the compaction tick emitted no span"

    def inside(e, outer):
        return (outer.ts_ns <= e.ts_ns
                and e.ts_ns + e.dur_ns <= outer.ts_ns + outer.dur_ns)

    for name in PHASES + ("decode_step", "prefill_step"):
        for e in spans[name]:
            assert inside(e, ticks[e.args["tick"]]), (name, e.args)
    for name in IN_STEP:
        assert len(spans[name]) == len(steps)
        for e in spans[name]:
            assert inside(e, steps[e.args["tick"]]), (name, e.args)
    for e in events:
        if e.name == "prefill_chunk":
            assert steps[e.args["tick"]].name == "prefill_step"

    # idle runs: never inside a step tick; the loop ends on one, and after
    # the last step tick nothing else is emitted by the scheduler
    idle = spans["sched_idle"]
    assert idle and all(e.args["tick"] not in ticks for e in idle)
    last = max(ticks.values(), key=lambda e: e.ts_ns)
    end = last.ts_ns + last.dur_ns
    after = [e for n in PHASES + ("sched_tick", "sched_idle")
             for e in spans[n] if e.ts_ns >= end]
    assert [e.name for e in after] == ["sched_idle"]
    assert after[0].args["polls"] >= 2         # many polls, one span
    assert after[0].args["tick"] == last.args["tick"] + 1
    # one clock anchor per step tick and per idle run
    assert _anchors(trace_dir) == len(ticks) + len(idle)


def test_decode_steps_count_the_live_lanes_of_the_grid(traced):
    """``lanes_grid`` is max_slots x lanes on every decode step, and
    ``lanes_live`` sums ceil(length / page_size) over the step's rows: a
    request of prompt L takes decode steps at lengths L + 1 .. L + max_new
    - 1, so over the run the counts add up to those lanes of every
    request."""
    events, _ = traced
    steps = [e for e in events if e.name == "decode_step"]
    assert steps
    grid = SC.max_slots * SC.lanes
    ps = SC.page_size
    for e in steps:
        assert e.args["lanes_grid"] == grid
        assert e.args["batch"] <= e.args["lanes_live"] \
            <= e.args["batch"] * SC.lanes
    rids = {e.args["rid"] for e in events if e.name == "first_token"}
    assert rids == set(range(5))
    assert not [e for e in events if e.name == "evict"]
    want = sum(-(-(_prompt_len(r) + k) // ps)
               for r in rids for k in range(1, MAX_NEW))
    assert sum(e.args["lanes_live"] for e in steps) == want
