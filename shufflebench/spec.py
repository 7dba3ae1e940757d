"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A configuration is the file its entry names; a traffic mix is
``traffic/<name>.json``; a per-layer metric is ``metrics/<name>.py``
with a ``read(run)`` function. Adding any of them takes a new file and
a new entry, never an edit of a file that exists.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, List

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HARNESS_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str, harness_dir: str = HARNESS_DIR) -> dict:
    return _load_json(os.path.join(harness_dir, "traffic", name + ".json"))


def load_reader(name: str, harness_dir: str = HARNESS_DIR) -> Callable:
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(harness_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "shufflebench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load_cell(workload: str, root: str = ROOT,
              harness_dir: str = HARNESS_DIR) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"shufflebench: no workload named {workload!r}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(workload, int(entry["chips"]), config,
                load_traffic(entry["traffic"], harness_dir), e2e, per_layer)


def peaks(device_kind: str, harness_dir: str = HARNESS_DIR) -> dict:
    """The published peaks of ``device_kind``; a kind missing from the
    table is an error, never a default."""
    table = _load_json(os.path.join(harness_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"shufflebench: no peaks for device kind "
                         f"{device_kind!r} in peaks.json")
    return table["devices"][device_kind]
