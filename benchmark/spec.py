"""``BENCHMARK.json`` and the files it names, found by name.

A cell's configuration is ``benchmark/configs/<config>.json`` (the file the
entry names), its traffic ``benchmark/workloads/<traffic>.json``, its
driver ``benchmark/drivers/<driver>.py`` (named by the traffic file), and
each per-layer metric's reader ``benchmark/metrics/<metric>.py`` (see
``reader``). Adding a
cell, a mix or a metric adds files and entries; no file is edited.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(spec: dict, name: str) -> dict:
    return _by_name(spec["workloads"], name, "workload")


def config_of(spec: dict, cell_entry: dict, root: str = ROOT) -> dict:
    entry = _by_name(spec["configs"], cell_entry["config"], "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic_of(cell_entry: dict, root: str = ROOT) -> dict:
    path = os.path.join(root, "benchmark", "workloads",
                        cell_entry["traffic"] + ".json")
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str, e2e_names: set) -> bool:
    """A per-layer metric with ``workloads`` is read in those cells; one
    without, in every cell that reports the metric it ``moves``."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric["moves"] in e2e_names


def end_to_end_of(spec: dict, cell_name: str) -> list[dict]:
    return [m for m in spec["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer_of(spec: dict, cell_name: str) -> list[dict]:
    e2e = {m["name"] for m in end_to_end_of(spec, cell_name)}
    return [m for m in spec["per_layer"] if _applies(m, cell_name, e2e)]


def load_by_path(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(traffic: dict, root: str = ROOT):
    return load_by_path(
        os.path.join(root, "benchmark", "drivers", traffic["driver"] + ".py"),
        "benchmark_driver_" + traffic["driver"])


def reader(metric_name: str, root: str = ROOT):
    """``metrics/<name>.py``, else the reader of the name without its last
    ``.<part>``: ``device_idle_pct.serve`` and ``device_idle_pct.sync``,
    one quantity split by the end-to-end metric it moves, share
    ``device_idle_pct.py``."""
    base = os.path.join(root, "benchmark", "metrics")
    name = metric_name
    while not os.path.exists(os.path.join(base, name + ".py")) and "." in name:
        name = name.rsplit(".", 1)[0]
    return load_by_path(os.path.join(base, name + ".py"),
                        "benchmark_metric_" + name.replace(".", "_"))
