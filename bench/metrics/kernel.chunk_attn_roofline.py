"""Kernels: the chunked-prefill attention kernel
(``kernels/paged_chunk_attn.py``) against its roofline: the least time for
the causal attention of each traced prefill tick's real rows over their
live tokens (``bench/work.py``), averaged per call, times the calls in the
device trace, over the kernel's device time.  Moves ttft_p50_ms."""

import statistics

from bench import work

KERNEL = r"chunk_attn(?!_quant)"


def read(ctx):
    p = ctx.profile
    if p is None or not p.chunk_rows:
        return None
    calls = p.ops(KERNEL)
    ns = sum(e.dur for e in calls)
    if not ns:
        return None
    least = statistics.mean(
        work.least_time(work.chunk_attn_call(ctx.dims, rows, ctx.chips),
                        ctx.peaks)[0] for rows in p.chunk_rows)
    return 100.0 * least * len(calls) / (ns / 1e9)
