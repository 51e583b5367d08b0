"""Whole prefill step: model FLOPs of the prefill chunks of the window
(each ``prefill_chunk`` event's position and length; the LM head once, at
the chunk that ends a prompt) over the ``prefill_step`` span time x chips
x the chip's bf16 peak.  Moves ttft_p50_ms."""

from bench import work


def read(ctx):
    tl = ctx.timeline
    plen = {r.rid: r.prompt_len for r in tl.every}
    flops = sum(work.prefill_chunk(ctx.dims, pos, n,
                                   pos + n >= plen.get(rid, 0))
                for t, rid, n, pos in tl.chunks if tl.w0 <= t < tl.w1)
    secs = sum(s.dur for s in tl.spans_in("prefill_step")) / 1e9
    if not secs or not flops:
        return None
    return 100.0 * flops / (secs * ctx.chips * ctx.peaks["bf16_flops_per_s"])
