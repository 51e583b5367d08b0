"""Whole decode step: model FLOPs of the window's decode steps (from
shapes, ``bench/work.py``, each row at its own context) over their span
time x chips x the chip's bf16 peak.  Moves tpot_p50_ms."""

from bench import work
from bench.e2e import decode_contexts


def read(ctx):
    tl = ctx.timeline
    steps = tl.spans.get("decode_step", [])
    ctxs = decode_contexts(tl, tl.every)
    flops = secs = 0.0
    for i, s in enumerate(steps):
        if tl.w0 <= s.t1 < tl.w1 and i in ctxs:
            flops += work.decode_step(ctx.dims, ctxs[i])
            secs += s.dur / 1e9
    if not secs:
        return None
    return 100.0 * flops / (secs * ctx.chips * ctx.peaks["bf16_flops_per_s"])
