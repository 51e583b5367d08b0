"""BRAVO leases: host time of the lease path per decode step, the
``lease_acquire`` (host read locks, stripe and model lease publishes) and
``lease_release`` spans of each ``decode_step`` that ends in the window
(matched by ``tick``), mean per step.  Moves tpot_p50_ms."""

import collections


def read(ctx):
    tl = ctx.timeline
    lease = collections.Counter()
    for name in ("lease_acquire", "lease_release"):
        for s in tl.spans.get(name, []):
            lease[s.args.get("tick")] += s.dur
    ticks = [s.args.get("tick") for s in tl.spans_in("decode_step")]
    ticks = [t for t in ticks if t is not None and t in lease]
    return sum(lease[t] for t in ticks) / len(ticks) / 1e6 if ticks \
        else None
