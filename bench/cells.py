"""Finds everything of a cell by name, so that a new configuration, traffic
mix or per-layer metric is new files plus entries in ``BENCHMARK.json``:

* ``configs/<config>.json`` -- the configuration as it is run (the file
  that ``BENCHMARK.json`` names); its ``model`` key names
  ``models/<model>.py``, the checkpoint, program builder, plain reference
  and cost functions shared by every configuration of that model;
* ``traffic/<traffic>.json`` -- the mix's parameters (``traffic_gen``);
* ``metrics/<stem>.py`` -- a per-layer metric's reader, ``read(run)``,
  found by the part of the metric's name before the first ``.``
  (``batch_fill.open`` -> ``batch_fill.py``);
* ``peaks.json`` -- each chip's peaks, by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class UnknownName(LookupError):
    """A name that BENCHMARK.json or a lookup refers to has no entry."""


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.exists(path):
        raise UnknownName(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: Dict[str, Any]          # the configuration's JSON
    traffic: Dict[str, Any]       # the traffic mix's JSON
    model: Any                    # models/<model>.py
    end_to_end: List[Dict]        # BENCHMARK.json entries this cell reports
    per_layer: List[Dict]


def _reports(metric, cell_name, e2e_names):
    """A metric with ``workloads`` is reported in the cells it lists; one
    without, in every cell (end-to-end) or in every cell that reports the
    end-to-end metric it ``moves`` (per-layer)."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def find_cell(name: str, *, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise UnknownName(f"no workload {name!r} in BENCHMARK.json; "
                          f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in configs:
        raise UnknownName(f"workload {name!r} names config {w['config']!r}, "
                          "which BENCHMARK.json does not list")
    spec = _json(os.path.join(root, configs[w["config"]]["file"]))
    e2e = [m for m in bm["end_to_end"] if _reports(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=int(w["chips"]), spec=spec,
        traffic=traffic(w["traffic"], bench_dir=bench_dir),
        model=model(spec["model"], bench_dir=bench_dir),
        end_to_end=e2e,
        per_layer=[m for m in bm["per_layer"]
                   if _reports(m, name, e2e_names)])


def traffic(name: str, *, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    path = os.path.join(bench_dir, "traffic", f"{name}.json")
    if not os.path.exists(path):
        raise UnknownName(f"no traffic mix file {path}")
    return _json(path)


def model(name: str, *, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "models", f"{name}.py"),
                       f"bench_model_{name}")


def metric_reader(name: str, *, bench_dir: str = BENCH_DIR):
    """The ``read(run)`` function of a per-layer metric: ``metrics/<stem>.py``
    for the part of the name before the first ``.``."""
    stem = name.split(".")[0]
    return load_module(os.path.join(bench_dir, "metrics", f"{stem}.py"),
                       f"bench_metric_{stem}").read


def peaks(device_kind: str, *, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = _json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["chips"]:
        raise UnknownName(f"no peaks for device_kind {device_kind!r} in "
                          f"peaks.json; have {sorted(table['chips'])}")
    return table["chips"][device_kind]
