"""The one traffic generator: every mix is a JSON file of parameters under
``bench/traffic/`` that this module reads.

A mix is open-loop sessions.  Sessions start by a thinned Poisson process
(a base rate, multiplied by ``burst.factor`` for ``burst.duty`` of every
``burst.period_s``).  Each session has 1..N turns; turn k+1 is due an
exponential gap after turn k, whatever the server did, so the loop never
closes.  Turn 1's prompt is an optional shared system prompt (one of a few,
picked by a bounded Zipf) plus the turn's own tokens; turn k+1's prompt is
turn k's prompt, plus a synthetic previous answer where the mix has one,
plus the new tokens, so each turn re-reads the pages of the last.

The SIZES (session start times, turn counts, gaps, system-prompt choice,
every length and ``max_new``) are drawn once from the mix's own
``population_seed``; the run's ``--seed`` draws only the token ids.  Every
seed therefore offers the same work at the same times: the spread between
seeds is the system's, not the generator's.

Adapted from ``repro.serving.loadgen`` (thinning, bounded Zipf, lognormal
lengths), extended with sessions, fixed work per seed and due times.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

__all__ = ["Due", "Schedule", "load_mix", "generate", "draw_lengths"]


@dataclasses.dataclass(frozen=True)
class Due:
    """One request of the schedule."""
    rid: int
    at_s: float            # due time, seconds from the window's start
    session: int
    turn: int              # 0-based
    sys_id: int            # shared system prompt, -1 for none
    prompt: np.ndarray     # (S,) int32
    max_new: int


@dataclasses.dataclass
class Schedule:
    requests: List[Due]    # sorted by due time
    preroll_s: float       # the schedule starts at -preroll_s
    window_s: float
    system_prompts: List[np.ndarray]    # the mix's shared prompts

    def in_window(self) -> List[Due]:
        return [r for r in self.requests if 0.0 <= r.at_s < self.window_s]


def load_mix(path) -> Dict:
    return json.loads(Path(path).read_text())


def draw_lengths(rng: np.random.Generator, spec: Optional[Dict],
                 n: int) -> np.ndarray:
    """``n`` lengths from a spec ``{"dist": "lognormal", "median", "sigma",
    "min", "max"}`` or ``{"dist": "uniform", "min", "max"}`` (inclusive);
    zeros where the spec is None."""
    if spec is None:
        return np.zeros(n, np.int64)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _rate(mix: Dict, t: float) -> float:
    r = float(mix["session_rate_per_s"])
    b = mix.get("burst")
    if b and b["factor"] > 1 and b["duty"] > 0:
        if (t % b["period_s"]) / b["period_s"] < b["duty"]:
            r *= b["factor"]
    return r


def _session_starts(rng: np.random.Generator, mix: Dict, t0: float,
                    t1: float) -> np.ndarray:
    """Thinned Poisson session starts over [t0, t1)."""
    b = mix.get("burst") or {}
    lam_max = float(mix["session_rate_per_s"]) * max(b.get("factor", 1), 1)
    out, t = [], t0
    while True:
        t += rng.exponential(1.0 / lam_max)
        if t >= t1:
            return np.asarray(out)
        if rng.random() < _rate(mix, t) / lam_max:
            out.append(t)


def _zipf(rng: np.random.Generator, n: int, k: int, s: float) -> np.ndarray:
    w = np.arange(1, k + 1, dtype=np.float64) ** (-s)
    return rng.choice(k, size=n, p=w / w.sum())


def generate(mix: Dict, *, seed: int, window_s: float, vocab: int,
             max_seq: int, session_rate: Optional[float] = None) -> Schedule:
    """The schedule of one run: every request due from ``-preroll_s`` to
    ``window_s + tail_s``.  ``session_rate`` overrides the mix's rate (for
    a rate sweep only)."""
    if session_rate is not None:
        mix = dict(mix, session_rate_per_s=session_rate)
    pre, tail = float(mix["preroll_s"]), float(mix["tail_s"])
    pop = np.random.default_rng(int(mix["population_seed"]))
    starts = _session_starts(pop, mix, -pre, window_s + tail)
    n = len(starts)
    t = mix["turns"]
    n_turns = pop.integers(t["min"], t["max"] + 1, n)
    m = int(n_turns.max()) if n else 0
    gaps = pop.exponential(float(mix["gap_s_mean"]), (n, m))
    first = draw_lengths(pop, mix["first"], n)
    follow = draw_lengths(pop, mix.get("followup"), n * m).reshape(n, m)
    answer = draw_lengths(pop, mix.get("answer"), n * m).reshape(n, m)
    max_new = draw_lengths(pop, mix["max_new"], n * m).reshape(n, m)
    sp = mix.get("system_prompts")
    sys_ids = (_zipf(pop, n, sp["count"], sp["zipf_s"]) if sp
               else np.full(n, -1))

    run = np.random.default_rng(int(seed))      # token ids only
    sys_tok = ([run.integers(1, vocab, sp["tokens"]).astype(np.int32)
                for _ in range(sp["count"])] if sp else [])
    reqs: List[Due] = []
    for s in range(n):
        at = starts[s]
        prompt = (sys_tok[sys_ids[s]] if sp else np.zeros(0, np.int32))
        own = run.integers(1, vocab, first[s]).astype(np.int32)
        prompt = np.concatenate([prompt, own])
        for k in range(int(n_turns[s])):
            if k:
                at += gaps[s, k - 1]
                extra = run.integers(1, vocab, answer[s, k - 1]
                                     + follow[s, k - 1]).astype(np.int32)
                prompt = np.concatenate([prompt, extra])
            if len(prompt) + max_new[s, k] > max_seq:
                break                    # the session has outgrown the
            #                              context: it ends here
            reqs.append(Due(rid=0, at_s=float(at), session=int(s),
                            turn=k, sys_id=int(sys_ids[s]), prompt=prompt,
                            max_new=int(max_new[s, k])))
    reqs.sort(key=lambda r: (r.at_s, r.session, r.turn))
    reqs = [r for r in reqs if r.at_s < window_s + tail]
    reqs = [dataclasses.replace(r, rid=i) for i, r in enumerate(reqs)]
    return Schedule(requests=reqs, preroll_s=pre, window_s=float(window_s),
                    system_prompts=sys_tok)
