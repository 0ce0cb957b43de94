"""BENCHMARK.json keeps the contract, every entry resolves to its files,
and a cell, mix or metric is added with new files and entries alone."""

import json
import os
import re
import shutil

import pytest

from benchmark import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_spec(ROOT)


def test_shape_of_the_file(bench):
    assert set(bench) == TOP
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert bench["paths"] == ["benchmark"]
    assert bench["command"][1] == "benchmark/run.py"
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for e in bench["configs"]:
        assert set(e) == {"name", "source", "file", "reduced", "why"}
    for e in bench["workloads"]:
        assert set(e) == {"name", "config", "traffic", "chips", "why"}
        assert e["chips"] in (1, 4) and len(e["why"]) <= 200
    for e in bench["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    for e in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for e in bench["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_every_entry_resolves(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert all(k in cfg for k in c["reduced"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        spec.config_of(bench, cell)
        traffic = spec.traffic_of(cell)
        assert hasattr(spec.driver(traffic), "setup")
        reported = {m["name"] for m in spec.end_to_end_of(bench,
                                                          cell["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        layers = spec.per_layer_of(bench, cell["name"])
        assert layers
        for m in layers:
            assert m["moves"] in reported
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert hasattr(spec.reader(m["name"]), "read")


def test_add_by_files_alone(tmp_path, bench):
    """A copy of the benchmark gains a configuration, a mix, a cell and a
    per-layer metric by new files and new entries; nothing is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = json.loads(json.dumps(bench))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ycsb_1kb.json")) as f:
        cfg = json.load(f)
    cfg["recordcount"] = 1 << 20
    (root / "benchmark" / "configs" / "ycsb_1m.json").write_text(
        json.dumps(cfg))
    (root / "benchmark" / "workloads" / "ycsb_c.json").write_text(
        json.dumps({"driver": "open_loop", "read_proportion": 1.0,
                    "requestdistribution": "zipfian"}))
    (root / "benchmark" / "metrics" / "probe_count.py").write_text(
        "def read(ctx):\n    return len(ctx.readings['probe']['lags'])\n")
    bench["configs"].append({"name": "ycsb_1m", "source": "x",
                             "file": "benchmark/configs/ycsb_1m.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "ycsb_c", "config": "ycsb_1m",
                               "traffic": "ycsb_c", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "tree_lag_p95_ms":
            m["workloads"].append("ycsb_c")
    bench["per_layer"].append({"name": "probe_count", "unit": "probes",
                               "better": "higher",
                               "source": "host_clock", "layer": "x",
                               "moves": "tree_lag_p95_ms",
                               "workloads": ["ycsb_c"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    loaded = spec.load_spec(str(root))
    cell = spec.cell(loaded, "ycsb_c")
    assert spec.config_of(loaded, cell, str(root))["recordcount"] == 1 << 20
    assert spec.traffic_of(cell, str(root))["read_proportion"] == 1.0
    assert spec.driver(spec.traffic_of(cell, str(root)), str(root))
    names = [m["name"] for m in spec.per_layer_of(loaded, "ycsb_c")]
    assert names == ["probe_count"]
    reader = spec.reader("probe_count", str(root))

    class Ctx:
        readings = {"probe": {"lags": [1.0, 2.0]}}

    assert reader.read(Ctx()) == 2
    # A quantity split by the metric it moves shares one reader.
    shared = spec.reader("device_idle_pct.sync", str(root))
    assert os.path.basename(shared.__file__) == "device_idle_pct.py"
