"""Finding a cell's files by the names in ``BENCHMARK.json``.

  configs/<config>.json     sizes, optimizer, batch, limits, source
  configs/<reference>.py    the configuration's plain reference
  traffic/<traffic>.json    the loop and its parameters
  workloads/<cell>.json     the cell's own parameters (override traffic)
  metrics/<metric>.py       one reader per metric: ``read(run)``
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench: dict, name: str, root: str = ROOT):
    """(workload entry, config, traffic parameters) of cell ``name``."""
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    config = _json(os.path.join(root, c["file"]))
    traffic = _json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    traffic.update(_json(os.path.join(HERE, "workloads",
                                      f"{name}.json")).get("params", {}))
    return w, config, traffic


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def metric_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
