"""Finds a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
lists the metrics.  Every piece is a file of its own:

- ``bench/configs/<config>.json``: the deployment (graphs, accelerators
  with their presets, memory systems, semantic engine) and its reference;
- ``bench/traffic/<traffic>.json``: clients, what one job asks for, the
  root pools and the warm-up;
- ``bench/metrics/<metric>.py``: one per-layer metric's reader.

Adding a cell, a configuration, a traffic mix or a metric adds files and
entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list  # (entry, reader module) pairs this cell reports


def load_metric(name: str):
    """The reader module ``bench/metrics/<name>.py`` (loaded by path, so a
    metric's name may hold dots)."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric:{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, benchmark: str | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration,
    traffic and metric readers loaded; KeyError for an unknown cell."""
    bm = load_json(benchmark or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {', '.join(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moved = {m["name"] for m in e2e}
    per_layer = [(m, load_metric(m["name"])) for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name, int(w["chips"]), w["config"], w["traffic"], config,
                traffic, e2e, per_layer)
