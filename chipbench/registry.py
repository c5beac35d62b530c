"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

Every part is a file, found by a name; adding a cell, configuration,
mix, generator, check or metric is adding files and entries: nothing
here, and nothing in the harness, changes.

* a cell (``workloads`` entry) names a configuration and a traffic mix;
* a configuration's entry names its file (``configs[].file``);
* a mix is ``chipbench/traffic/<mix>.json``;
* a mix's ``"generator"`` (default ``"sequential"``) is
  ``chipbench/generators/<name>.py``, whose ``make(mix, seed)`` returns
  the run's traffic;
* each name in a configuration's ``"checks"`` is
  ``chipbench/checks/<name>.py``, whose ``LIMITS`` and ``read`` (and
  optionally ``control``) join the common comparison
  (``chipbench/correct.py``);
* a per-layer metric is read by ``chipbench/metrics/<metric>.py``'s
  ``read(ctx)``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

DEFAULT_GENERATOR = "sequential"


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


def _module(kind: str, name: str, root: str):
    """``chipbench/<kind>/<name>.py``, loaded from its path."""
    path = os.path.join(root, "chipbench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``chipbench/metrics/<name>.py``."""
    return _module("metrics", name, root).read


def readers(entries: List[dict], root: str = ROOT) -> Dict[str, object]:
    return {m["name"]: reader(m["name"], root) for m in entries}


def generator(mix: dict, root: str = ROOT):
    """The ``make(mix, seed)`` function of the generator the mix names."""
    return _module("generators", mix.get("generator", DEFAULT_GENERATOR), root).make


def checks(cfg: dict, root: str = ROOT) -> list:
    """The modules of the check files the configuration names, in order."""
    return [_module("checks", name, root) for name in cfg.get("checks", ())]
