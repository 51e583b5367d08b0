"""Scheduler: host time between back-to-back steps, the next tick's
``step_dispatch`` start minus this tick's ``step_readback`` end, mean over
the pairs of step ticks with consecutive ``tick`` ids whose spans end in
the window (an idle poll between two steps breaks the pair).  Moves
tpot_p50_ms: the device waits for the host through every such gap."""


def read(ctx):
    tl = ctx.timeline
    dispatch = {s.args.get("tick"): s for s in tl.spans_in("step_dispatch")}
    gaps = [dispatch[s.args["tick"] + 1].t0 - s.t1
            for s in tl.spans_in("step_readback")
            if s.args.get("tick") is not None
            and s.args["tick"] + 1 in dispatch]
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
