"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix; the configuration entry
names its file; the mix is ``chipbench/traffic/<mix>.json``; a per-layer
metric is read by ``chipbench/metrics/<metric>.py``. Adding a cell,
configuration, mix or metric is adding files and entries: nothing here
changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def mix(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "chipbench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def metrics_for(bench: dict, cell_name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    without a ``workloads`` key, and those whose key lists the cell."""
    return [
        m for m in bench[kind]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(root, "chipbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def readers(entries: List[dict], root: str = ROOT) -> Dict[str, object]:
    return {m["name"]: reader(m["name"], root) for m in entries}
