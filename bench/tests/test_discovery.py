"""Cells, configurations, mixes and per-layer metrics are found by name:
a new one is new files and new entries, with no edit to the harness.
Also the committed BENCHMARK.json against the contract's shape rules."""

import json
import re
import shutil
from pathlib import Path

from bench import spec

REPO = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_a_new_cell_is_files_and_entries(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((tmp_path / b["configs"][0]["file"]).read_text())
    cfg["name"] = "dummy-model"
    (tmp_path / "bench/configs/dummy-model.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/dummy-mix.json").write_text(
        json.dumps({"session_rate_per_s": 0.1}))
    (tmp_path / "bench/metrics/dummy.rows.py").write_text(
        "def read(ctx):\n    return 7.0 if ctx else None\n")
    b["configs"].append(dict(b["configs"][0], name="dummy-model",
                             file="bench/configs/dummy-model.json"))
    b["workloads"].append({"name": "dummy-model.dummy-mix",
                           "config": "dummy-model", "traffic": "dummy-mix",
                           "chips": 1, "why": "a test"})
    b["per_layer"].append({"name": "dummy.rows", "unit": "rows",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine scheduler",
                           "moves": "tpot_p50_ms",
                           "workloads": ["dummy-model.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("dummy-model.dummy-mix", root=tmp_path)
    assert cell.config["name"] == "dummy-model"
    assert cell.traffic == {"session_rate_per_s": 0.1}
    assert [m["name"] for m in cell.per_layer] == ["dummy.rows"]
    assert spec.metric_reader("dummy.rows", root=tmp_path)(object()) == 7.0
    # the cells already there are untouched by the new entries
    old = spec.load_cell(b["workloads"][0]["name"], root=tmp_path)
    assert "dummy.rows" not in [m["name"] for m in old.per_layer]


def test_benchmark_json_follows_the_contract():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    cfgs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert (REPO / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
        cell = spec.load_cell(w["name"])
        assert cell.per_layer and len(cell.end_to_end) >= 2
    for m in b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert callable(spec.metric_reader(m["name"]))
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")
    assert len(json.dumps(b)) < 64 * 1024
