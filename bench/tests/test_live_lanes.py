"""The share of the decode kernel's grid that fetches and computes,
``kernel.paged_attn_live_lanes``, read from the ``decode_step`` spans'
``lanes_live`` / ``lanes_grid`` counts on a synthetic timeline."""

from types import SimpleNamespace as NS

from bench import spec
from bench.e2e import build_timeline

MS = 1_000_000
W0, W1 = 100 * MS, 1_100 * MS


def ev(ts, name, dur, **args):
    return NS(ts_ns=ts, cat="engine", name=name, dur_ns=dur, args=args)


def read(events):
    tl = build_timeline(events, [], W0, W1)
    return spec.metric_reader("kernel.paged_attn_live_lanes")(
        NS(timeline=tl))


def test_live_lane_share_of_the_decode_grid():
    events = [
        # ends before the window: not counted
        ev(20 * MS, "decode_step", 60 * MS, batch=3,
           lanes_live=90, lanes_grid=100),
        ev(200 * MS, "decode_step", 20 * MS, batch=2,
           lanes_live=7, lanes_grid=100),
        ev(300 * MS, "decode_step", 20 * MS, batch=3,
           lanes_live=13, lanes_grid=100),
        ev(400 * MS, "prefill_step", 20 * MS, rows=1),
    ]
    assert read(events) == 10.0          # 20 of 200


def test_nothing_to_read_without_the_counts():
    """A program whose decode spans carry no lane counts reads None."""
    assert read([ev(200 * MS, "decode_step", 20 * MS, batch=2)]) is None
    assert read([]) is None
