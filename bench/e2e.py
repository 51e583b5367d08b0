"""End-to-end arithmetic over one run's request records and engine spans.

Times are ``time.monotonic_ns`` readings, the clock of the program's trace
events.  A request's TTFT is timed from its DUE time in the schedule, so a
stall that delays later requests shows in their TTFT; a request that never
produced its first token, or never finished, is a miss (+inf) and sorts
last.  Percentiles are nearest-rank over every request due in the window.
"""

from __future__ import annotations

import bisect
import dataclasses
import math
from typing import Dict, Iterable, List, Optional, Sequence

__all__ = ["Req", "Span", "Timeline", "nearest_rank", "build_timeline"]

INF = float("inf")


@dataclasses.dataclass
class Req:
    rid: int
    due_ns: int
    prompt_len: int
    max_new: int
    submit_ns: Optional[int] = None
    admit_ns: Optional[int] = None       # first admission
    cached: int = 0                      # prompt tokens served from cache
    first_ns: Optional[int] = None       # first token
    done_ns: Optional[int] = None
    tokens: int = 0
    evictions: int = 0

    @property
    def served(self) -> bool:
        return self.first_ns is not None and self.done_ns is not None

    def ttft_ms(self) -> float:
        return (self.first_ns - self.due_ns) / 1e6 if self.served else INF

    def tpot_ms(self) -> Optional[float]:
        """(done - first token) / (tokens - 1); None for a one-token
        answer, +inf for a miss."""
        if not self.served:
            return INF
        if self.tokens < 2:
            return None
        return (self.done_ns - self.first_ns) / (self.tokens - 1) / 1e6

    def queue_ms(self) -> float:
        return ((self.admit_ns - self.due_ns) / 1e6
                if self.admit_ns is not None else INF)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    t0: int
    dur: int
    args: Dict

    @property
    def t1(self) -> int:
        return self.t0 + self.dur


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile by nearest rank (misses are +inf and count)."""
    if not values:
        raise ValueError("no samples")
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


@dataclasses.dataclass
class Timeline:
    w0: int                       # window start (ns)
    w1: int                       # window end (ns)
    reqs: List[Req]               # every request due in the window
    every: List[Req]              # every request of the schedule
    spans: Dict[str, List[Span]]  # engine spans by name, by start
    first_tokens: List[int]       # every first-token instant (all requests)
    chunks: List[tuple]           # prefill chunks: (ts, rid, chunk, pos)

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) / 1e9

    def spans_in(self, name: str, t0: Optional[int] = None,
                 t1: Optional[int] = None) -> List[Span]:
        """Spans of ``name`` that END inside [t0, t1) (the window by
        default): a step's tokens exist once it has ended."""
        t0 = self.w0 if t0 is None else t0
        t1 = self.w1 if t1 is None else t1
        return [s for s in self.spans.get(name, []) if t0 <= s.t1 < t1]

    def failed(self) -> List[Req]:
        return [r for r in self.reqs if not r.served]

    def ttft_ms(self, q: float) -> float:
        return nearest_rank([r.ttft_ms() for r in self.reqs], q)

    def tpot_ms(self, q: float) -> float:
        vals = [v for v in (r.tpot_ms() for r in self.reqs) if v is not None]
        return nearest_rank(vals, q)

    def output_tokens(self) -> int:
        """Tokens produced inside the window: one per first token (made
        by a prefill tick) and one per row of every decode step."""
        first = sum(1 for t in self.first_tokens if self.w0 <= t < self.w1)
        return first + sum(s.args.get("batch", 0)
                           for s in self.spans_in("decode_step"))

    def output_tok_s(self) -> float:
        return self.output_tokens() / self.window_s


def build_timeline(events: Iterable, due: Sequence, w0: int,
                   w1: int) -> Timeline:
    """Fold TRACER events into per-request records for every request of
    ``due`` (objects with ``rid``, ``at_s``, ``prompt``, ``max_new``;
    ``at_s`` counts from ``w0``) and engine spans by name; the timeline's
    ``reqs`` are those due in [w0, w1).  The first
    admission and first token are kept: a preempted request that is
    re-admitted keeps its original times."""
    reqs = {d.rid: Req(rid=d.rid, due_ns=w0 + int(round(d.at_s * 1e9)),
                       prompt_len=len(d.prompt), max_new=d.max_new)
            for d in due}
    spans: Dict[str, List[Span]] = {}
    first_tokens: List[int] = []
    chunks: List[tuple] = []
    for e in events:
        if e.cat == "engine" and e.dur_ns:
            spans.setdefault(e.name, []).append(
                Span(e.name, e.ts_ns, e.dur_ns, e.args or {}))
            continue
        if e.cat != "req" or not e.args:
            continue
        if e.name == "first_token":
            first_tokens.append(e.ts_ns)
        elif e.name == "prefill_chunk":
            chunks.append((e.ts_ns, e.args.get("rid"),
                           int(e.args.get("chunk", 0)),
                           int(e.args.get("pos", 0))))
        r = reqs.get(e.args.get("rid"))
        if r is None:
            continue
        if e.name == "submit" and r.submit_ns is None:
            r.submit_ns = e.ts_ns
        elif e.name == "admit" and r.admit_ns is None:
            r.admit_ns = e.ts_ns
            r.cached = int(e.args.get("cached", 0))
        elif e.name == "first_token" and r.first_ns is None:
            r.first_ns = e.ts_ns
        elif e.name == "done":
            r.done_ns = e.ts_ns
            r.tokens = int(e.args.get("tokens", 0))
        elif e.name == "evict":
            r.evictions += 1
    for v in spans.values():
        v.sort(key=lambda s: s.t0)
    every = sorted(reqs.values(), key=lambda r: r.due_ns)
    return Timeline(w0=w0, w1=w1,
                    reqs=[r for r in every if w0 <= r.due_ns < w1],
                    every=every, spans=spans,
                    first_tokens=sorted(first_tokens), chunks=chunks)


def decode_contexts(tl: Timeline, reqs: Iterable[Req]) -> Dict[int, List[int]]:
    """Context length each served request attended over at each decode
    step it took part in: -> {index of the decode span: [context, ...]}.
    Request r's k-th decode step after its first token attends over
    prompt + k tokens; its steps are the decode spans between its first
    token and its last (a request evicted mid-decode is left out)."""
    steps = tl.spans.get("decode_step", [])
    starts = [s.t0 for s in steps]
    out: Dict[int, List[int]] = {}
    for r in reqs:
        if not r.served or r.evictions or r.tokens < 2:
            continue
        i = bisect.bisect_right(starts, r.first_ns)
        for k in range(1, r.tokens):
            if i >= len(steps) or steps[i].t0 > r.done_ns:
                break
            out.setdefault(i, []).append(r.prompt_len + k)
            i += 1
    return out
