"""Device: share of the traced window in which no op ran, averaged over
the chips (1 - union of op intervals / window).  Moves tpot_p50_ms."""


def read(ctx):
    p = ctx.profile
    if p is None or not p.window_ns:
        return None
    return 100.0 * (1.0 - p.busy_total_ns / (p.window_ns * len(p.busy_ns)))
