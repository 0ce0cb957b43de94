"""The ycsb_d cell on the CPU at a tiny size, the look for a chip skipped:
it comes out correct, its traced run reads the new per-layer metrics (and
no device metric), ``lost_write`` fails the check; and the new readers
read the program's spans and counters, or nothing where it lacks them."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark import spec

ROOT = spec.ROOT
HELPER = os.path.join(ROOT, "benchmark", "tests", "cpu_run.py")

START = {"span.device.restructure.count": 10,
         "span.device.restructure.total_us": 200_000,
         "span.device.key_index.count": 12,
         "span.device.key_index.total_us": 6_000,
         "device.structural_batches": 10,
         "device.pump_batches": 12}
# Over the window: 40 structural flushes of 25 ms, 50 lookups of 0.4 ms,
# 50 publishes.
END = {"span.device.restructure.count": 50,
       "span.device.restructure.total_us": 1_200_000,
       "span.device.key_index.count": 62,
       "span.device.key_index.total_us": 26_000,
       "device.structural_batches": 50,
       "device.pump_batches": 62}
EXPECT = {"restructure_ms": 25.0, "key_index_ms": 0.4,
          "structural_share": 0.8}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_new_readers(name):
    ctx = SimpleNamespace(readings={"metrics.a.start": START,
                                    "metrics.a.end": END})
    assert spec.reader(name).read(ctx) == pytest.approx(EXPECT[name])
    bare = SimpleNamespace(readings={"metrics.a.start": {},
                                     "metrics.a.end": {}})
    assert spec.reader(name).read(bare) is None


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, HELPER, "ycsb_d", *argv], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=400)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_ycsb_d_is_correct_on_the_cpu():
    res = _run("0")
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["programs_readied_in_window"]["value"] == 0
    assert set(res["metrics"]) == {"tree_lag_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_ycsb_d_traced_run_reads_the_insert_path():
    res = _run("1")
    assert res["correct"] is True, res["checks"]
    assert {"restructure_ms", "key_index_ms",
            "structural_share"} <= set(res["metrics"])
    assert "device_idle_pct.insert" not in res["metrics"]
    assert res["metrics"]["structural_share"]["value"] > 0


def test_ycsb_d_lost_write_fails_the_check():
    res = _run("0", "lost_write")
    assert res["correct"] is False, res["checks"]
