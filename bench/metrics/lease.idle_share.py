"""BRAVO leases: share of the traced window, averaged over the chips, in
which the device is idle while the scheduler is inside ``lease_acquire``
or ``lease_release``, with the spans placed on the trace's clock by the
program's clock anchors (``bench/hostclock.py``).  Moves tpot_p50_ms."""

from bench import hostclock


def read(ctx):
    if ctx.profile is None or not any(
            ctx.timeline.spans.get(n) for n in hostclock.LEASE_SPANS):
        return None
    h = hostclock.place(ctx)
    if h is None or not ctx.profile.window_ns:
        return None
    idle = sum(h.idle_ns.get(n, 0) for n in hostclock.LEASE_SPANS)
    return 100.0 * idle / h.chips / ctx.profile.window_ns
