"""Everything a run needs, found by name from BENCHMARK.json: the cell's
configuration file, its traffic mix (`benchmark/traffic/<traffic>.json`),
one reader per metric (`benchmark/metrics/<metric>.py`, whose
`read(window)` returns the number, or None when it finds nothing to read)
and the checks its configuration names (`reference.load_check`). A cell, a
configuration, a mix, a metric or a check is added by adding files and
entries; nothing here names one. A configuration whose pod templates state
a field the pod factory does not know is refused here, when it is loaded.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

from benchmark.deploy import check_template

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str = ROOT


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    for t_name, t in config["templates"].items():
        check_template(t, where=f"{w['config']}: template {t_name}")
    with open(os.path.join(root, "benchmark", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=w["chips"], config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
                root=root)


def load_reader(metric: str, root: str = ROOT) -> Callable:
    path = os.path.join(root, "benchmark", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], window, root: str = ROOT) -> Dict:
    """{name: {"value", "unit"}} for every metric whose reader found a
    number; a reader that finds nothing leaves its metric out."""
    out = {}
    for m in metrics:
        v = load_reader(m["name"], root)(window)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
