"""Finds a cell's parts by the names in ``BENCHMARK.json``.

Each part is a file of its own, so a later change adds a configuration,
a traffic mix, an entry or a per-layer metric by adding a file and an
entry, and edits nothing that is there:

* ``configs/<name>.json``  a configuration (the file BENCHMARK.json names);
* ``traffic/<name>.json``  a traffic mix; its ``entry`` names the entry;
* ``entries/<entry>.py``   drives one kind of system entry (``run(cell)``);
* ``metrics/<name>.py``    reads one per-layer metric (``read(run)``);
* ``limits/<workload>.json`` the limits of a cell's comparison.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from types import ModuleType

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                   f"{[w['name'] for w in bench['workloads']]})")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def config_file(name: str, here: str = HERE) -> dict:
    """A configuration by its file name under ``configs/``."""
    return _json(os.path.join(here, "configs", name + ".json"))


def traffic(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "traffic", name + ".json"))


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise KeyError(f"no module at {path}")
    if name in sys.modules:
        return sys.modules[name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def entry(name: str) -> ModuleType:
    return importlib.import_module(f"perfbench.entries.{name}")


def metric(name: str, here: str = HERE) -> ModuleType:
    return _module(os.path.join(here, "metrics", name + ".py"),
                   "perfbench_metric_" + name.replace(".", "_"))


def end_to_end_of(bench: dict, wl_name: str):
    """The end-to-end metrics a cell reports."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or wl_name in m["workloads"]]


def per_layer_of(bench: dict, wl_name: str):
    """The per-layer metrics a cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_of(bench, wl_name)}
    return [m for m in bench["per_layer"]
            if (wl_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
