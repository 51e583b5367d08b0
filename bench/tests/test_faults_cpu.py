"""The check must fail a run whose timed path is broken underneath: a
served token altered where the scheduler records it, and a request that
never finishes."""

import jax
import pytest

from bench import device, run, serve, spec
from bench.tests import tiny
from repro.serving import scheduler as S


@pytest.fixture()
def cell(tmp_path, monkeypatch):
    monkeypatch.setattr(serve, "pool_pages", lambda *a: 10**6)
    return spec.load_cell("tiny.chat", root=tiny.make_root(tmp_path))


def _measure(cell):
    return run.measure(cell, 977, 3.0, False, jax.devices()[:1],
                       peaks=device.peaks("TPU v5 lite"))


def test_altered_token_is_not_correct(cell, monkeypatch):
    orig = S.Scheduler.on_token
    vocab = tiny.MODEL["vocab"]
    monkeypatch.setattr(S.Scheduler, "on_token", lambda self, st, tok:
                        orig(self, st, (tok + 1) % vocab))
    res = _measure(cell)
    assert not res["correct"]
    gap = res["limits"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_lost_request_is_not_correct(cell, monkeypatch):
    orig = S.Scheduler.finish

    def finish(self, st):
        orig(self, st)
        if st.rid % 2 and st.rid < serve.WARM_RID:   # never handed back
            st.request = None

    monkeypatch.setattr(S.Scheduler, "finish", finish)
    cell.traffic["drain_s"] = 2.0
    res = _measure(cell)
    assert not res["correct"]
    assert res["failed"] >= 1
    assert res["limits"]["failed_requests"]["value"] == res["failed"]
