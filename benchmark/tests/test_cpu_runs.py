"""Whole runs on the CPU at a tiny size, the look for a chip skipped:
each cell comes out correct, a traced run prints no device metric, a run
without a TPU prints nothing, and each planted fault fails the check."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import spec

ROOT = spec.ROOT
HELPER = os.path.join(ROOT, "benchmark", "tests", "cpu_run.py")
DEVICE_METRICS = {"device_idle_pct.serve"}


def _run(*argv, timeout=400):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=timeout)
    return out


def _result(out):
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["ycsb_a"])
def test_cell_is_correct_on_the_cpu(cell):
    res = _result(_run(HELPER, cell, "0"))
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["programs_readied_in_window"]["value"] == 0
    assert res["device"]["platform"] == "cpu"
    bench = spec.load_spec()
    want = {m["name"] for m in spec.end_to_end_of(bench, cell)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_traced_run_prints_no_device_metric_off_the_chip():
    res = _result(_run(HELPER, "ycsb_a", "1"))
    assert res["correct"] is True
    assert not DEVICE_METRICS & set(res["metrics"])
    assert "busy_s" not in res["device"] and "breakdown" not in res


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(os.path.join(ROOT, "benchmark", "run.py"), "--workload",
               "ycsb_a", "--seed", "1", "--seconds", "1", "--trace", "0",
               timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("cell,fault", [
    ("ycsb_a", "replication_gap"),
    ("ycsb_a", "lost_write"),
    ("ycsb_a", "altered_answer"),
    ("ycsb_a", "half_batch"),
])
def test_planted_fault_fails_the_check(cell, fault):
    res = _result(_run(HELPER, cell, "0", fault))
    assert res["correct"] is False, res["checks"]
