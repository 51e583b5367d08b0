"""Reduction of a device trace (the JAX profiler's ``.xplane.pb``) to the
numbers the per-layer metrics read.

Only JAX reads the file (``jax.profiler.ProfileData``).  Per chip (a
``/device:TPU:<n>`` plane) the reduction keeps the ``XLA Ops`` line (one
event per HLO op: its name, and its ``long_name`` / ``tf_op`` stats where
the profiler gives them) and the ``XLA Modules`` line (one event per
program run: ``jit_<function>``).

* busy time: the union of the op intervals of a chip (modules where a chip
  has no op line); the idle share is 1 - busy / traced window;
* time in an op or a program: the sum of its events' durations, matched
  by a regular expression on the op's name and stats or the module name;
* ``breakdown``: the ops that took most device time, and the longest idle
  gaps, each named by the programs that ran before and after it.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Ev", "Chip", "Reduced", "load", "union_ns", "reduce_chips",
           "reduce_run"]


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: int        # ns, the trace's clock
    dur: int          # ns
    text: str = ""    # the op's name, long name and framework op, joined

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Chip:
    ops: List[Ev]
    modules: List[Ev]


def union_ns(evs: Sequence[Ev]) -> Tuple[int, List[Tuple[int, int]]]:
    """Length of the union of the intervals, and the merged intervals."""
    merged: List[Tuple[int, int]] = []
    for e in sorted(evs, key=lambda e: e.start):
        if merged and e.start <= merged[-1][1]:
            if e.end > merged[-1][1]:
                merged[-1] = (merged[-1][0], e.end)
        else:
            merged.append((e.start, e.end))
    return sum(b - a for a, b in merged), merged


def _stat_text(ev) -> str:
    """Every stat value of an event (long name, framework op, source...),
    joined: kernels and programs are matched by name anywhere in it."""
    try:
        return " ".join(str(v) for _, v in ev.stats)
    except Exception:          # an event the profiler gave no stats
        return ""


def load(trace_dir: str) -> Dict[str, Chip]:
    """Chips of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {}
    data = ProfileData.from_file(max(paths, key=os.path.getsize))
    chips: Dict[str, Chip] = {}
    for plane in data.planes:
        if not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        ops: List[Ev] = []
        mods: List[Ev] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [Ev(e.name, int(e.start_ns), int(e.duration_ns),
                          e.name + " " + _stat_text(e)) for e in line.events]
            elif line.name == "XLA Modules":
                mods = [Ev(e.name, int(e.start_ns), int(e.duration_ns),
                           e.name) for e in line.events]
        if ops or mods:
            chips[plane.name] = Chip(ops, mods)
    return chips


@dataclasses.dataclass
class Reduced:
    chips: Dict[str, Chip]
    window_ns: int                       # the traced window (host clock)
    busy_ns: Dict[str, int]
    breakdown: Dict
    decode_ctx: List[List[int]] = dataclasses.field(default_factory=list)
    chunk_rows: List[List[Tuple[int, int]]] = dataclasses.field(
        default_factory=list)

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def busy_total_ns(self) -> int:
        return sum(self.busy_ns.values())

    def ops(self, pattern: str) -> List[Ev]:
        rx = re.compile(pattern)
        return [e for c in self.chips.values() for e in c.ops
                if rx.search(e.text)]

    def op_ns(self, pattern: str) -> int:
        return sum(e.dur for e in self.ops(pattern))

    def module_ns(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(e.dur for c in self.chips.values() for e in c.modules
                   if rx.search(e.name))


def _module_at(mods: List[Ev], t: int, before: bool) -> str:
    """Name of the program that ended last before ``t`` (or started first
    after it), without the profile's program fingerprint."""
    if before:
        c = [m for m in mods if m.end <= t]
        m = max(c, key=lambda m: m.end) if c else None
    else:
        c = [m for m in mods if m.start >= t]
        m = min(c, key=lambda m: m.start) if c else None
    return re.sub(r"\(\d+\)$", "", m.name) if m else "-"


def reduce_chips(chips: Dict[str, Chip], window_ns: int,
                 top: int = 10) -> Reduced:
    busy: Dict[str, int] = {}
    by_op: collections.Counter = collections.Counter()
    gaps: List[Tuple[int, str]] = []
    for name, c in chips.items():
        evs = c.ops or c.modules
        busy[name], merged = union_ns(evs)
        for e in c.ops:
            by_op[_op_label(e)] += e.dur
        for (a0, a1), (b0, _) in zip(merged, merged[1:]):
            gaps.append((b0 - a1, f"{_module_at(c.modules, a1, True)} -> "
                                  f"{_module_at(c.modules, b0, False)}"))
    n = max(len(chips), 1)
    gap_sum: collections.Counter = collections.Counter()
    for g, label in gaps:
        gap_sum[label] += g
    breakdown = {
        "device_ops": [[k, v / n / 1e9] for k, v in by_op.most_common(top)],
        "idle_gaps": [[k, v / n / 1e9]
                      for k, v in gap_sum.most_common(top)]}
    return Reduced(chips=chips, window_ns=window_ns, busy_ns=busy,
                   breakdown=breakdown)


def _op_label(e: Ev) -> str:
    """A stable label for an op: its HLO instruction name without the
    instance number, and the first non-scalar shape of its result (the
    TPU profile names an op by its HLO text: ``%while = (s32[],
    bf16[4,256,2304], ...``)."""
    head, _, rest = e.name.partition(" = ")
    shape = re.search(r"[a-z]+\d*\[\d[\d,]*\]", rest)
    label = re.sub(r"\.\d+", "", head) + (f" {shape.group(0)}" if shape
                                           else "")
    return label[:160]


def reduce_run(trace_dir: str, run) -> Optional[Reduced]:
    """The run's trace, with the host's decode-step contexts and prefill
    rows of the traced window (for the kernels' work)."""
    from bench.e2e import decode_contexts
    if run.trace_ns is None:
        return None
    chips = load(trace_dir)
    if not chips:
        return None
    t0, t1 = run.trace_ns
    red = reduce_chips(chips, t1 - t0)
    tl = run.timeline
    ctxs = decode_contexts(tl, tl.every)
    for i, s in enumerate(tl.spans.get("decode_step", [])):
        if t0 <= s.t0 and s.t1 <= t1 and i in ctxs:
            red.decode_ctx.append(ctxs[i])
    steps = [s for s in tl.spans.get("prefill_step", [])
             if t0 <= s.t0 and s.t1 <= t1]
    for s in steps:
        # a tick's chunk events follow its span on the scheduler thread
        nxt = min((o.t0 for o in tl.spans.get("prefill_step", [])
                   if o.t0 > s.t0), default=t1)
        rows = [(pos, n) for ts, _, n, pos in tl.chunks if s.t1 <= ts < nxt]
        if rows:
            red.chunk_rows.append(rows)
    return red
