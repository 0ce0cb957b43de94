"""Protocol-level throughput benchmarks with the reference's enforced floors.

Mirrors the reference suite's thresholds
(/root/reference/tests/integration/test_benchmark.py):
  SET  > 1,000 ops/s (avg < 100 ms)      [:177-180]
  GET  > 2,000 ops/s (avg <  50 ms)      [:212-215]
  mixed > 800 ops/s (avg <  80 ms)       [:249-252]
  >= 95% of 50 concurrent connections OK [:316-317]
  10-client throughput >= 0.5x 1-client  [:341-343]

The native server clears these floors by orders of magnitude; the asserts
keep the SAME numbers as the reference so regressions trip the same wire.
"""

import os
import threading
import time

import pytest

from merklekv_tpu.client import MerkleKVClient
from merklekv_tpu.native_bindings import NativeEngine, NativeServer

pytestmark = pytest.mark.benchmark


@pytest.fixture
def server():
    eng = NativeEngine("mem")
    srv = NativeServer(eng, "127.0.0.1", 0)
    srv.start()
    yield srv
    srv.close()
    eng.close()


def _hammer(port, n_clients, ops_per_client, op):
    """Run op(client, client_id, i) from n_clients threads; return
    (total_ops, wall_seconds, latencies, errors)."""
    lat: list[float] = []
    errors: list[Exception] = []
    lock = threading.Lock()

    def worker(cid):
        try:
            with MerkleKVClient("127.0.0.1", port) as c:
                local = []
                for i in range(ops_per_client):
                    t0 = time.perf_counter()
                    op(c, cid, i)
                    local.append(time.perf_counter() - t0)
                with lock:
                    lat.extend(local)
        except Exception as e:  # pragma: no cover
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return n_clients * ops_per_client, wall, lat, errors


def test_set_throughput_floor(server):
    n, wall, lat, errors = _hammer(
        server.port, 5, 400, lambda c, cid, i: c.set(f"s{cid}:{i}", f"v{i}")
    )
    assert not errors
    ops_s = n / wall
    avg_ms = 1000 * sum(lat) / len(lat)
    print(f"\nSET: {ops_s:,.0f} ops/s, avg {avg_ms:.3f} ms")
    assert ops_s > 1000  # reference floor
    assert avg_ms < 100


def test_get_throughput_floor(server):
    with MerkleKVClient("127.0.0.1", server.port) as c:
        c.mset({f"g{i}": f"v{i}" for i in range(1000)})
    n, wall, lat, errors = _hammer(
        server.port, 5, 400, lambda c, cid, i: c.get(f"g{i % 1000}")
    )
    assert not errors
    ops_s = n / wall
    avg_ms = 1000 * sum(lat) / len(lat)
    print(f"\nGET: {ops_s:,.0f} ops/s, avg {avg_ms:.3f} ms")
    assert ops_s > 2000  # reference floor
    assert avg_ms < 50


def test_mixed_workload_floor(server):
    def op(c, cid, i):
        if i % 3 == 0:
            c.set(f"m{cid}:{i}", f"v{i}")
        elif i % 3 == 1:
            c.get(f"m{cid}:{i - 1}")
        else:
            c.delete(f"m{cid}:{i - 2}")

    n, wall, lat, errors = _hammer(server.port, 10, 150, op)
    assert not errors
    ops_s = n / wall
    avg_ms = 1000 * sum(lat) / len(lat)
    print(f"\nmixed: {ops_s:,.0f} ops/s, avg {avg_ms:.3f} ms")
    assert ops_s > 800  # reference floor
    assert avg_ms < 80


def test_concurrent_connections(server):
    ok = []
    lock = threading.Lock()

    def connect_and_op(i):
        try:
            with MerkleKVClient("127.0.0.1", server.port, timeout=30) as c:
                c.set(f"conn{i}", "x")
                assert c.get(f"conn{i}") == "x"
            with lock:
                ok.append(i)
        except Exception:
            pass

    threads = [threading.Thread(target=connect_and_op, args=(i,)) for i in range(50)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert time.perf_counter() - t0 < 30
    assert len(ok) >= 48  # >= 95% of 50


def test_scalability_sanity(server):
    """10-client aggregate throughput >= 0.5x single-client throughput."""

    def run(n_clients):
        n, wall, _, errors = _hammer(
            server.port, n_clients, 300,
            lambda c, cid, i: c.set(f"sc{cid}:{i}", "v"),
        )
        assert not errors
        return n / wall

    single = run(1)
    ten = run(10)
    print(f"\n1 client: {single:,.0f} ops/s; 10 clients: {ten:,.0f} ops/s")
    assert ten >= 0.5 * single


def test_pipeline_throughput(server):
    """Pipelined batches: the native server drains whole request buffers."""
    with MerkleKVClient("127.0.0.1", server.port) as c:
        cmds = [f"SET p{i} v{i}" for i in range(5000)]
        t0 = time.perf_counter()
        out = c.pipeline(cmds)
        wall = time.perf_counter() - t0
        assert all(r == "OK" for r in out)
        ops_s = len(cmds) / wall
        print(f"\npipelined SET: {ops_s:,.0f} ops/s")
        assert ops_s > 10_000  # reference's claimed sustained throughput


def test_kernel_bench_tool_smoke(monkeypatch, capfd):
    """tools/kernel_bench.py runs end-to-end off-TPU and emits valid JSON
    rows for the scan baselines (the Pallas rows are chip-only)."""
    import json
    import runpy

    # Lazy backend check: collection must not import (let alone claim) the
    # jax backend for a module whose other tests are jax-free.
    import jax

    if jax.default_backend() == "tpu":
        pytest.skip(
            "smoke run is the off-TPU path; on-chip kernels are covered by "
            "tests/test_sha256_pallas.py, and the full 4M-leaf bench does "
            "not belong inside the suite"
        )

    monkeypatch.setenv("MKV_KB_REPS", "2")
    runpy.run_path(
        os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "tools", "kernel_bench.py"),
        run_name="__main__",
    )
    out = capfd.readouterr().out
    rows = [json.loads(line) for line in out.strip().splitlines()]
    kernels = {r["kernel"] for r in rows}
    assert {"sha256_blocks_scan", "sha256_node_pairs_scan",
            "build_levels_dispatch"} <= kernels
    assert all(r["ms"] > 0 for r in rows)


def test_bench_failure_still_emits_json_record(monkeypatch, capsys):
    """The driver contract hardening (VERDICT top-next): when the data
    plane dies — no TPU, no working jax, whatever — bench.main() must
    still leave ONE parsable JSON record on stdout and return normally
    (a failed round once regressed to rc=1 with parsed=null)."""
    import json

    import bench

    monkeypatch.setattr(bench, "_resolve_backend", lambda: "cpu")

    def boom(*a, **kw):
        raise RuntimeError("backend exploded mid-bench")

    monkeypatch.setattr(bench, "bench_cpu", boom)
    bench.main()  # must not raise
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rec["metric"] == "merkle_rebuild_diff_keys_per_s"
    assert rec["value"] is None
    assert "backend exploded" in rec["error"]
    assert rec["backend"] == "cpu"


def test_backend_probe_is_bounded(monkeypatch):
    """probe_default_backend resolves in a subprocess and respects its
    deadline — a hung backend init can no longer wedge the bench."""
    from merklekv_tpu.utils.jaxenv import probe_default_backend

    # A CPU-pinned environment short-circuits without a subprocess.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert probe_default_backend(timeout=0.001) == "cpu"
    # Unpinned, an absurdly short deadline forces the timeout path
    # deterministically (the child is spawned and killed) — the exact
    # degradation a hung backend init produces.
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert probe_default_backend(timeout=0.001) is None


def test_bench_main_rc0_under_poisoned_jax_platforms():
    """Regression for a failed backend init: a real `python bench.py`
    subprocess with JAX_PLATFORMS poisoned to an unusable platform must
    STILL exit 0 with one parsable JSON record on stdout — the raw
    `jax.default_backend()` crash path must stay routed through the
    bounded-probe/fallback contract."""
    import json
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "bogusplatform"  # pinned AND unusable
    env["MKV_BENCH_PROBE_TIMEOUT"] = "15"
    out = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=root,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, (out.returncode, out.stderr[-2000:])
    records = [
        ln for ln in out.stdout.splitlines() if ln.strip().startswith("{")
    ]
    assert records, f"no JSON on stdout: {out.stdout!r}"
    rec = json.loads(records[-1])
    assert rec["metric"] == "merkle_rebuild_diff_keys_per_s"
    # A poisoned platform cannot produce a number; the record must carry
    # the failure instead of the process carrying a traceback + rc 1.
    assert rec["value"] is None
    assert rec.get("error")
