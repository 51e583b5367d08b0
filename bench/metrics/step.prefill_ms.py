"""Model step: mean ``prefill_step`` span.  Moves ttft_p50_ms: a
request's first token comes out of its last prefill tick."""


def read(ctx):
    steps = ctx.timeline.spans_in("prefill_step")
    return sum(s.dur for s in steps) / len(steps) / 1e6 if steps else None
