"""BRAVO leases: share of the device's busy time spent in the lease
programs (the fused publish / release by index, the scan and poll
kernels, the stripe-lane and mask programs, the denied-reader fold, the
cache-length bump), by their program names in the trace.  Moves
tpot_p50_ms."""

LEASE = (r"acquire|release|publish|_scan|_poll|fold_denied|stripe_lanes"
         r"|mask|scrub|scatter|lambda")


def read(ctx):
    p = ctx.profile
    if p is None or not p.busy_total_ns:
        return None
    return 100.0 * p.module_ns(LEASE) / p.busy_total_ns
