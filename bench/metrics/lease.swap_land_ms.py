"""BRAVO leases: mean ``swap_land`` span (model-lane revocation, bounded
drain, install) of the weight swaps that land in the window.  Moves
tpot_p50_ms."""


def read(ctx):
    swaps = ctx.timeline.spans_in("swap_land")
    return sum(s.dur for s in swaps) / len(swaps) / 1e6 if swaps else None
