"""The scheduler loop's spans: every phase of a step tick nests inside
its ``sched_tick`` (and the lease, dispatch and read-back phases inside
the step span) under the same ``tick``; a run of idle iterations is one
``sched_idle`` span with no phase spans; and each step tick and each idle
run puts one clock anchor on the profiler's timeline."""

from __future__ import annotations

import collections
import glob
import os
import time

import jax
import numpy as np
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


def _serve_traced(trace_dir: str):
    cfg = configs.get_smoke("minicpm-2b")      # the benchmark's CPU widths
    sc = SchedulerConfig(max_slots=4, page_size=8, max_seq=160,
                         prefill_chunk=16, prefill_rows=2, token_budget=32)
    eng = ServingEngine(
        cfg, M.init_params(jax.random.PRNGKey(0), cfg),
        mesh=Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                  ("data", "model")),
        rules=MeshRules(), n_pages=96, scheduler=sc,
        engine_cfg=EngineConfig(idle_poll_s=IDLE_POLL_S))
    rng = np.random.default_rng(0)

    def serve(rids, compact=False):
        reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab, 12 + 9 * i)
                        .astype(np.int32), max_new=4) for i in rids]
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


def test_tick_phases_nest_and_an_idle_run_is_one_span(tmp_path):
    events = _serve_traced(str(tmp_path))
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
    assert _anchors(str(tmp_path)) == len(ticks) + len(idle)
