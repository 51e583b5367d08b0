"""Scheduler: queue wait, first admission minus due time, median over
every request due in the window (a request never admitted is +inf).
Moves ttft_p50_ms."""

from bench.e2e import nearest_rank


def read(ctx):
    reqs = ctx.timeline.reqs
    return nearest_rank([r.queue_ms() for r in reqs], 0.5) if reqs else None
