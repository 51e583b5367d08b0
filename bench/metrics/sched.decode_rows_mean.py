"""Scheduler: rows per decode step, the mean of the ``batch`` of every
``decode_step`` span that ends in the window.  Moves tpot_p50_ms."""


def read(ctx):
    steps = ctx.timeline.spans_in("decode_step")
    if not steps:
        return None
    return sum(s.args.get("batch", 0) for s in steps) / len(steps)
