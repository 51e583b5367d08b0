"""Kernels: the share of the decode-attention kernel's grid that fetches
and computes.  Each ``decode_step`` span carries ``lanes_live`` (the sum
over its rows of ceil(length / page_size): the lanes the bounded page walk
runs) and ``lanes_grid`` (max_slots x lanes: the grid the kernel is
launched over); the metric is sum(lanes_live) / sum(lanes_grid) over the
decode steps that end in the window, in percent.  Nothing to read where
the spans carry no such counts.  Moves tpot_p50_ms."""


def read(ctx):
    steps = [s for s in ctx.timeline.spans_in("decode_step")
             if "lanes_live" in s.args and s.args.get("lanes_grid")]
    if not steps:
        return None
    live = sum(s.args["lanes_live"] for s in steps)
    return 100.0 * live / sum(s.args["lanes_grid"] for s in steps)
