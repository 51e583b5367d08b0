"""The traffic generator: the same seed gives the same schedule, every
seed the same work at the same times with other token ids, and the
schedule has the lengths, sharing and session structure its mix states."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
# no system prompt, each completion extends the last prompt by 32-512
# tokens: the generator's other session shape (code completion)
CODE = {"population_seed": 7, "session_rate_per_s": 0.5,
        "burst": {"factor": 3, "period_s": 10, "duty": 0.2},
        "system_prompts": None, "turns": {"min": 1, "max": 4},
        "gap_s_mean": 3.0,
        "first": {"dist": "lognormal", "median": 2048, "sigma": 0.6,
                  "min": 256, "max": 7680},
        "answer": None, "followup": {"dist": "uniform", "min": 32,
                                     "max": 512},
        "max_new": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                    "min": 8, "max": 256},
        "preroll_s": 12, "tail_s": 60}
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json")) + ["code"]
LIMITS = {"chat-swap": (122753, 3072), "code": (49152, 8192)}


def gen(name, seed, window=45.0, **kw):
    mix = dict(CODE) if name == "code" else \
        loadgen.load_mix(TRAFFIC / f"{name}.json")
    mix.update(kw)
    vocab, max_seq = LIMITS.get(name, (1000, 4096))
    return mix, loadgen.generate(mix, seed=seed, window_s=window,
                                 vocab=vocab, max_seq=max_seq)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    _, a = gen(name, 2**31 + 5)
    _, b = gen(name, 2**31 + 5)
    assert [(r.at_s, r.max_new) for r in a.requests] == \
        [(r.at_s, r.max_new) for r in b.requests]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    _, a = gen(name, 1)
    _, b = gen(name, 987654321)
    # the same requests at the same due times; only the token ids differ
    assert [(r.at_s, r.session, r.turn, r.sys_id, len(r.prompt), r.max_new)
            for r in a.requests] == \
        [(r.at_s, r.session, r.turn, r.sys_id, len(r.prompt), r.max_new)
         for r in b.requests]
    assert not any(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_sessions_follow_the_mix(name):
    mix, s = gen(name, 42)
    vocab, max_seq = LIMITS[name]
    sp = mix.get("system_prompts")
    by_session = {}
    for r in s.requests:
        assert len(r.prompt) + r.max_new <= max_seq
        assert mix["max_new"]["min"] <= r.max_new <= mix["max_new"]["max"]
        assert r.prompt.min() >= 1 and r.prompt.max() < vocab
        by_session.setdefault(r.session, []).append(r)
    for turns in by_session.values():
        turns.sort(key=lambda r: r.turn)
        assert len(turns) <= mix["turns"]["max"]
        first = turns[0]
        own = len(first.prompt) - (sp["tokens"] if sp else 0)
        assert mix["first"]["min"] <= own <= mix["first"]["max"]
        for a, b in zip(turns, turns[1:]):
            assert b.at_s > a.at_s                    # open-loop gaps
            assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
            grow = len(b.prompt) - len(a.prompt)
            lo = mix["followup"]["min"] + (mix["answer"] or {"min": 0})["min"]
            assert grow >= lo
    if sp:
        heads = {r.prompt[:sp["tokens"]].tobytes() for r in s.requests}
        assert len(heads) <= sp["count"]
        ids = np.bincount([r.sys_id for r in s.requests],
                          minlength=sp["count"])
        assert ids[0] == ids.max()                    # Zipf: rank 0 leads


def test_lognormal_median_and_uniform_bounds():
    rng = np.random.default_rng(0)
    x = loadgen.draw_lengths(rng, {"dist": "lognormal", "median": 128,
                                   "sigma": 0.7, "min": 16, "max": 1024},
                             20000)
    assert 120 <= np.median(x) <= 136 and x.min() >= 16 and x.max() <= 1024
    u = loadgen.draw_lengths(rng, {"dist": "uniform", "min": 32,
                                   "max": 512}, 20000)
    assert u.min() == 32 and u.max() == 512


def test_bursts_raise_the_session_rate():
    mix, s = gen("chat-swap", 3, window=400.0, preroll_s=0.0, tail_s=0.0,
                 turns={"min": 1, "max": 1})
    b = mix["burst"]
    t = np.asarray([r.at_s for r in s.requests])
    inb = (t % b["period_s"]) / b["period_s"] < b["duty"]
    rate_in = inb.sum() / (400 * b["duty"])
    rate_out = (~inb).sum() / (400 * (1 - b["duty"]))
    assert 2.0 < rate_in / rate_out < 4.5


def test_mix_files_are_data():
    for p in TRAFFIC.glob("*"):
        assert p.suffix in (".json", ".jsonl", ".toml", ".txt", ".csv")
        if p.suffix == ".json":
            json.loads(p.read_text())
