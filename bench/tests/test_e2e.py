"""End-to-end arithmetic: TTFT from the due time, misses counted at +inf
in the percentile, tokens counted only inside the window, TPOT per
request, and decode contexts per step."""

import math
from types import SimpleNamespace as NS

from bench.e2e import build_timeline, decode_contexts, nearest_rank

MS = 1_000_000


def ev(ts, cat, name, dur=0, **args):
    return NS(ts_ns=ts, cat=cat, name=name, dur_ns=dur, args=args)


def due(rid, at_s, n=10, max_new=4):
    return NS(rid=rid, at_s=at_s, prompt=[1] * n, max_new=max_new)


def test_nearest_rank_counts_misses():
    vals = [float(i) for i in range(1, 10)] + [math.inf]
    assert nearest_rank(vals, 0.9) == 9.0
    assert nearest_rank(vals + [math.inf], 0.9) == math.inf
    assert nearest_rank([5.0], 0.9) == 5.0


def test_ttft_from_due_time_and_misses():
    w0 = 1_000 * MS
    sched = [due(0, 0.0), due(1, 0.5), due(2, 0.9), due(3, 2.0)]
    events = [
        # request 0: submitted 20 ms late, admitted at 50, first at 80
        ev(w0 + 20 * MS, "req", "submit", rid=0),
        ev(w0 + 50 * MS, "req", "admit", rid=0, cached=4),
        ev(w0 + 80 * MS, "req", "first_token", rid=0),
        ev(w0 + 110 * MS, "req", "done", rid=0, tokens=4),
        # request 1: served
        ev(w0 + 600 * MS, "req", "admit", rid=1, cached=0),
        ev(w0 + 700 * MS, "req", "first_token", rid=1),
        ev(w0 + 730 * MS, "req", "done", rid=1, tokens=4),
        # request 2: admitted, never finished -> a miss
        ev(w0 + 950 * MS, "req", "admit", rid=2, cached=0),
    ]
    tl = build_timeline(events, sched, w0, w0 + 1_000 * MS)
    assert [r.rid for r in tl.reqs] == [0, 1, 2]      # 3 is after the window
    assert tl.reqs[0].ttft_ms() == 80.0               # from due, not admit
    assert tl.reqs[0].queue_ms() == 50.0
    assert tl.reqs[0].tpot_ms() == 10.0               # 30 ms / 3 gaps
    assert [r.rid for r in tl.failed()] == [2]
    assert tl.ttft_ms(0.9) == math.inf
    assert tl.tpot_ms(0.9) == math.inf
    assert tl.ttft_ms(0.5) == 200.0         # rank 2 of [80, 200, inf]
    assert tl.tpot_ms(0.5) == 10.0


def test_tokens_only_inside_the_window():
    w0, w1 = 100 * MS, 1_100 * MS
    events = [
        ev(50 * MS, "req", "first_token", rid=9),        # before: no
        ev(150 * MS, "req", "first_token", rid=8),       # inside: 1
        ev(20 * MS, "engine", "decode_step", 60 * MS, batch=5),   # ends 80
        ev(90 * MS, "engine", "decode_step", 20 * MS, batch=3),   # ends 110
        ev(1_090 * MS, "engine", "decode_step", 20 * MS, batch=7),  # 1110
    ]
    tl = build_timeline(events, [], w0, w1)
    assert tl.output_tokens() == 1 + 3
    assert tl.output_tok_s() == 4.0


def test_decode_contexts_follow_the_request():
    steps = [ev(t * MS, "engine", "decode_step", MS, batch=1)
             for t in (10, 20, 30, 40)]
    events = steps + [
        ev(5 * MS, "req", "first_token", rid=0),
        ev(35 * MS, "req", "done", rid=0, tokens=4),
        ev(15 * MS, "req", "first_token", rid=1),
        ev(45 * MS, "req", "done", rid=1, tokens=2),
    ]
    tl = build_timeline(events, [due(0, 0.0, n=100), due(1, 0.0, n=7)],
                        0, 100 * MS)
    ctx = decode_contexts(tl, tl.every)
    assert ctx == {0: [101], 1: [102, 8], 2: [103]}

