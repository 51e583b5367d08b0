"""Kernels: the decode-attention kernel (``kernels/paged_attn.py``) against
its roofline.  The least time the chip needs for the live tokens' K/V,
queries and outputs of each call (``bench/work.py``; bound by memory at
these shapes), averaged over the traced window's decode steps, times the
calls the device trace holds, over the kernel's device time.  Moves
tpot_p50_ms."""

import statistics

from bench import work

KERNEL = r"paged_attn(?!_quant)"


def read(ctx):
    p = ctx.profile
    if p is None or not p.decode_ctx:
        return None
    calls = [e for e in p.ops(KERNEL) if "chunk" not in e.text]
    ns = sum(e.dur for e in calls)
    if not ns:
        return None
    least = statistics.mean(
        work.least_time(work.paged_attn_call(ctx.dims, c, ctx.chips),
                        ctx.peaks)[0] for c in p.decode_ctx)
    return 100.0 * least * len(calls) / (ns / 1e9)
