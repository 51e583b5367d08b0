"""Everything a run reads, found by NAME: the cell in ``BENCHMARK.json``,
its configuration file, its traffic mix, and one reader file per
per-layer metric.  A later cell, configuration, mix or metric is new files
and new entries; nothing here changes.

* ``BENCHMARK.json`` — ``workloads[name]`` names ``config`` and
  ``traffic``; ``configs[config].file`` is the configuration's JSON.
* ``bench/traffic/<traffic>.json`` — the mix's parameters, read by
  ``bench/loadgen.py``.
* ``bench/metrics/<metric>.py`` — a ``read(ctx)`` that returns the metric
  or None where the run has nothing to read for it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict               # the configuration file's contents
    config_entry: Dict         # its entry in BENCHMARK.json
    traffic_name: str
    traffic: Dict              # the mix's parameters
    end_to_end: List[Dict]     # metrics this cell reports at --trace 0
    per_layer: List[Dict]      # ... and at --trace 1
    bench: Dict                # the whole BENCHMARK.json


def _applies(metric: Dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                config_entry=entry, traffic_name=w["traffic"],
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                bench=bench)


def metric_reader(metric: str, root: Path = ROOT) -> Callable:
    """``read(ctx)`` from ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
