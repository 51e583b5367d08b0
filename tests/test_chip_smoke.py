"""``chip_smoke.py`` driven on the CPU: its whole serving-and-checking run
at the minicpm smoke widths (Pallas kernels interpreted), and its refusal
to start without a TPU."""

import importlib.util
import json
import pathlib
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.serving.scheduler import SchedulerConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolve it by name
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        del sys.modules["chip_smoke"]


def test_run_at_smoke_widths(chip_smoke, monkeypatch, capsys):
    # on the CPU the kernels run interpreted (no Mosaic custom calls) and
    # the backend reports no HBM: the test relaxes those two chip checks
    monkeypatch.setattr(chip_smoke, "require_kernels", lambda *a: None)
    monkeypatch.setattr(chip_smoke, "pool_pages", lambda d, b, want: want)
    cfg = configs.get_smoke("minicpm-2b")
    _, rules, _ = configs.get("minicpm-2b")
    traffic = chip_smoke.Traffic(
        prompt_lens=(37, 37, 30, 21, 12), shared=17, max_new=4,
        quant_requests=2,
        sched=SchedulerConfig(max_slots=4, page_size=8, max_seq=48,
                              prefill_chunk=16, prefill_rows=2,
                              token_budget=32))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    chip_smoke.run(cfg, rules, mesh, traffic, seed=0)
    out = capsys.readouterr().out
    assert "bf16 pool vs dense" in out and "int8 pool vs dense" in out
    assert "weight swaps 1 (failures 0)" in out


def test_refuses_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
