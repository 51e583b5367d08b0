"""The scheduler thread's spans placed on the device trace's clock.

The program's spans are ``time.monotonic_ns`` readings; the device trace
has the JAX profiler's own clock.  The program marks its clock on the
profiler's timeline: an ``obs.clock`` annotation with a ``mono_ns`` stat,
one per step tick and one per idle run (``repro.obs.clock_anchor``).  For
each anchor, its start on the trace clock minus ``mono_ns`` is the offset
between the clocks plus the latency of entering the annotation, which
only adds: the smallest difference is the offset (``clock_offset_ns``),
and the median one's excess over it says how far a typical anchor strays
(``anchor_jitter_ns``).

The device planes are not aligned with the host plane that well: a
decode program can appear to start before the host began to dispatch it
(``device_early_ns``: at least 1.4-1.6 ms on a TPU v5e).  Each chip's
ops are moved later by the least amount under which none does, and each
stretch of device idle time in the traced window (the window less the
merged op intervals of a chip) is then put down to the innermost
scheduler span that covers it (``idle_by_host_span``; ``-`` where none
does).  The traced window is the one ``bench/serve.py`` takes: it ends
``TRACE_S / 2`` after the middle of the measured window, and the
reduction gives its length.

The harness reduces only the device planes of the trace, so the anchors
are read here from the trace file itself: the run keeps it in a
``bench-trace-*`` directory under the temporary directory until the
readers have run, and the file is the one whose device planes match the
reduced profile.  A program without anchors gives no offset, and the
readers built on it give nothing.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import statistics
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

from bench.profile import union_ns
from bench.serve import TRACE_S

__all__ = ["ANCHOR", "SCHED_SPANS", "LEASE_SPANS", "Placed", "read_trace",
           "clock_offset", "innermost", "idle_intervals", "idle_by_span",
           "placed_from", "place"]

ANCHOR = "obs.clock"   # repro.obs.CLOCK_ANCHOR, not imported: a traced run
#                        also reads a program that has no anchors
# the spans of the engine's scheduler thread (``swap_land`` and the other
# engine spans come from other threads)
SCHED_SPANS = ("sched_tick", "sched_idle", "tick_compact", "tick_admit",
               "tick_grow", "lease_acquire", "step_dispatch",
               "lease_release", "step_readback", "tick_finish",
               "decode_step", "prefill_step")
LEASE_SPANS = ("lease_acquire", "lease_release")
DECODE_MODULE = r"^jit_decode\b"     # the paged decode step's program
TRACE_DIRS = "bench-trace-*"         # bench/run.py's trace directories

Piece = Tuple[int, int, str]


@dataclasses.dataclass
class Placed:
    clock_offset_ns: int          # trace clock - monotonic clock
    anchor_jitter_ns: int         # median anchor's excess over the offset
    anchors: int
    idle_ns: Dict[str, int]       # device idle ns by span, summed over chips
    chips: int
    decode_outside_ns: Optional[int]  # farthest a decode program ran
    #                                   outside its decode_step span
    device_early_ns: Optional[int]  # farthest a decode program starts
    #                                 before its step_dispatch span starts:
    #                                 how far, at least, the profile's
    #                                 device plane runs ahead of its host
    #                                 plane


def _fingerprint_chips(chips) -> Dict[str, Tuple]:
    return {name: (len(c.ops), len(c.modules),
                   min((m.start for m in c.modules), default=None))
            for name, c in chips.items()}


def read_trace(path: str) -> Tuple[Dict[str, Tuple], List[Tuple[int, int]]]:
    """-> (device planes' fingerprint, anchors as (trace ns, mono ns))."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    prints: Dict[str, Tuple] = {}
    anchors: List[Tuple[int, int]] = []
    for plane in data.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            n_ops = n_mods = 0
            first = None
            for line in plane.lines:
                if line.name == "XLA Ops":
                    n_ops = sum(1 for _ in line.events)
                elif line.name == "XLA Modules":
                    starts = [int(e.start_ns) for e in line.events]
                    n_mods = len(starts)
                    first = min(starts, default=None)
            if n_ops or n_mods:
                prints[plane.name] = (n_ops, n_mods, first)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name != ANCHOR:
                        continue
                    anchors += [(int(e.start_ns), int(v))
                                for k, v in e.stats if k == "mono_ns"]
    return prints, anchors


def _run_anchors(chips) -> Optional[List[Tuple[int, int]]]:
    """Anchors of the trace file the profile ``chips`` was reduced from,
    or None where no such file is left."""
    want = _fingerprint_chips(chips)
    paths = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        try:
            prints, anchors = read_trace(path)
        except RuntimeError:          # a file a killed run left half written
            continue
        if prints == want:
            return anchors
    return None


def clock_offset(anchors: Sequence[Tuple[int, int]]) -> Tuple[int, int]:
    """-> (offset, jitter): the least (trace - mono) of the anchors, and
    the median one's excess over it."""
    d = [t - m for t, m in anchors]
    lo = min(d)
    return lo, int(statistics.median(d)) - lo


def innermost(spans: Sequence[Piece]) -> List[Piece]:
    """The union of ``spans`` (start, end, name) cut into pieces, each
    named by the shortest span that covers it: on one thread spans nest,
    so that is the innermost."""
    marks = sorted({t for a, b, _ in spans for t in (a, b)})
    by_start = sorted(spans)
    out: List[Piece] = []
    active: List[Piece] = []
    i = 0
    for x, y in zip(marks, marks[1:]):
        active = [s for s in active if s[1] > x]
        while i < len(by_start) and by_start[i][0] <= x:
            if by_start[i][1] > x:
                active.append(by_start[i])
            i += 1
        if active:
            out.append((x, y, min(active, key=lambda s: s[1] - s[0])[2]))
    return out


def idle_intervals(merged: Sequence[Tuple[int, int]],
                   window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The stretches of ``window`` that the merged busy intervals leave."""
    lo, hi = window
    out = []
    t = lo
    for a, b in merged:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def idle_by_span(gaps: Sequence[Tuple[int, int]],
                 pieces: Sequence[Piece]) -> Dict[str, int]:
    """Ns of each gap by the piece that covers it, ``-`` for the rest
    (both sorted and without overlaps)."""
    out: Dict[str, int] = collections.Counter()
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        covered = 0
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            x, y, name = pieces[k]
            ov = min(b, y) - max(a, x)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        if b - a > covered:
            out["-"] += b - a - covered
    return out


def _nearest(spans: Sequence[Tuple[int, int]], starts: Sequence[int],
             t: int) -> Optional[Tuple[int, int]]:
    """The span of ``spans`` (sorted) whose start lies nearest ``t``."""
    i = bisect.bisect_right(starts, t)
    near = spans[max(0, i - 1):i + 1]
    return min(near, key=lambda s: abs(s[0] - t)) if near else None


def _decode_fit(mods, steps, dispatches) -> Tuple[List[int], List[int]]:
    """For each decode program run: how far it lies outside its
    decode_step span (0 inside), and how far it starts before the
    dispatch that launched it began (0 after)."""
    s_starts = [a for a, _ in steps]
    d_starts = [a for a, _ in dispatches]
    outside, early = [], []
    for m in mods:
        st = _nearest(steps, s_starts, m.start)
        if st is not None:
            outside.append(max(0, st[0] - m.start) + max(0, m.end - st[1]))
        d = _nearest(dispatches, d_starts, m.start)
        if d is not None:
            early.append(max(0, d[0] - m.start))
    return outside, early


def placed_from(profile, timeline,
                anchors: Optional[Sequence[Tuple[int, int]]],
                window: Tuple[int, int]) -> Optional[Placed]:
    """Device idle of ``profile`` in ``window`` (monotonic ns) by the
    scheduler spans of ``timeline``, placed with ``anchors``; None without
    anchors or scheduler spans."""
    spans = [(s.t0, s.t1, s.name) for name in SCHED_SPANS
             for s in timeline.spans.get(name, [])]
    if not anchors or not spans or profile is None or not profile.chips:
        return None
    off, jitter = clock_offset(anchors)
    pieces = innermost([(a + off, b + off, n) for a, b, n in spans])
    steps = sorted((s.t0 + off, s.t1 + off)
                   for s in timeline.spans.get("decode_step", []))
    decode_ticks = {s.args.get("tick")
                    for s in timeline.spans.get("decode_step", [])}
    dispatches = sorted((s.t0 + off, s.t1 + off)
                        for s in timeline.spans.get("step_dispatch", [])
                        if s.args.get("tick") in decode_ticks)
    idle: Dict[str, int] = collections.Counter()
    outside: List[int] = []
    early: List[int] = []
    rx = re.compile(DECODE_MODULE)
    win = (window[0] + off, window[1] + off)
    for c in profile.chips.values():
        o, e = _decode_fit([m for m in c.modules if rx.search(m.name)],
                           steps, dispatches)
        outside += o
        early += e
        # the profile places a chip's ops ahead of its host plane (decode
        # programs start before the host began to dispatch them): move
        # them by the least amount that makes every one start after
        lead = max(e, default=0)
        _, merged = union_ns(c.ops or c.modules)
        merged = [(a + lead, b + lead) for a, b in merged]
        idle.update(idle_by_span(idle_intervals(merged, win), pieces))
    return Placed(clock_offset_ns=off, anchor_jitter_ns=jitter,
                  anchors=len(anchors), idle_ns=dict(idle),
                  chips=len(profile.chips),
                  decode_outside_ns=max(outside) if outside else None,
                  device_early_ns=max(early) if early else None)


def place(ctx) -> Optional[Placed]:
    """:func:`placed_from` for a run's reader context, found once per
    profile.  Adds ``idle_by_host_span`` (seconds per chip, by span) and
    ``host_clock`` to the profile's breakdown, which the traced run
    prints."""
    p = ctx.profile
    if p is None:
        return None
    if not hasattr(p, "host_clock"):
        tl = ctx.timeline
        spans = any(tl.spans.get(n) for n in SCHED_SPANS)
        anchors = _run_anchors(p.chips) if spans and p.chips else None
        end = (tl.w0 + tl.w1) // 2 + int(TRACE_S / 2 * 1e9)
        p.host_clock = placed_from(p, tl, anchors, (end - p.window_ns, end))
        h = p.host_clock
        if h is not None:
            p.breakdown["idle_by_host_span"] = [
                [k, v / h.chips / 1e9] for k, v in
                sorted(h.idle_ns.items(), key=lambda kv: -kv[1])]
            p.breakdown["host_clock"] = {
                "clock_offset_ns": h.clock_offset_ns,
                "anchor_jitter_ns": h.anchor_jitter_ns,
                "anchors": h.anchors,
                "decode_outside_ns": h.decode_outside_ns,
                "device_early_ns": h.device_early_ns}
    return p.host_clock
