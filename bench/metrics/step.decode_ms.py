"""Model step: mean ``decode_step`` span (lease publish, dispatch, device
work and the token read back on the host).  Moves tpot_p50_ms."""


def read(ctx):
    steps = ctx.timeline.spans_in("decode_step")
    return sum(s.dur for s in steps) / len(steps) / 1e6 if steps else None
