"""Device chaos through a REAL node process (the CI device-chaos step).

A spawned ``python -m merklekv_tpu`` server on the 8-way host-platform
mesh, with a persistent sharded-dispatch failure injected via the
``MKV_DEVICE_FAULTS`` env hook (the process-level seam the guard reads in
spawned processes): the node must come up, stay live, land the serving
tree on the surviving single-device rung, and answer HASH bit-identically
to the independent CPU golden chain — the degradation ladder working
end-to-end through config, __main__, the native server, and the cluster
callback, not just in-process objects.
"""

import os
import socket
import subprocess
import sys
import time

import pytest

from merklekv_tpu.client import MerkleKVClient
from merklekv_tpu.merkle.cpu import MerkleTree

pytestmark = pytest.mark.integration

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait_port(port, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            return
        except OSError:
            time.sleep(0.05)
    raise TimeoutError(f"port {port} never came up")


def test_node_survives_persistent_shard_failure(tmp_path):
    procs = []
    try:
        broker = subprocess.Popen(
            [sys.executable, "-m", "merklekv_tpu.broker", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=dict(os.environ, PYTHONPATH=REPO),
        )
        procs.append(broker)
        line = broker.stdout.readline()
        assert "listening on" in line, line
        broker_port = int(line.rsplit(":", 1)[1].split()[0])

        cfg = tmp_path / "chaos.toml"
        cfg.write_text(
            f"""
host = "127.0.0.1"
port = 0
engine = "mem"

[replication]
enabled = true
mqtt_broker = "127.0.0.1"
mqtt_port = {broker_port}
topic_prefix = "devchaos"
client_id = "chaos-node"

[device]
sharding = "8"
max_staleness_ms = 100
dispatch_deadline_ms = 120000
"""
        )
        node = subprocess.Popen(
            [sys.executable, "-m", "merklekv_tpu", "--config", str(cfg)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=dict(
                os.environ,
                PYTHONPATH=REPO,
                JAX_PLATFORMS="cpu",
                # The chaos hook: every sharded dispatch in the spawned
                # process fails persistently (environment-shaped).
                MKV_DEVICE_FAULTS="fail:shard*",
            ),
        )
        procs.append(node)
        line = node.stdout.readline()
        assert "listening on" in line, line
        port = int(line.rsplit(":", 1)[1].split()[0])
        _wait_port(port)

        golden = MerkleTree()
        with MerkleKVClient("127.0.0.1", port, timeout=30.0) as c:
            for i in range(64):
                c.set(f"chaos:{i:03d}", f"v{i}")
                golden.insert(f"chaos:{i:03d}", f"v{i}")
            # Poll HASH until the mirror warms (riding the ladder down to
            # the single-device rung under the injected fault) and the
            # pump window closes. The node must answer EVERY poll — a
            # wedged or dead node fails here, which is the point.
            deadline = time.time() + 180
            level = None
            while time.time() < deadline:
                assert c.ping(), "node stopped answering under the fault"
                if c.hash() == golden.root_hex():
                    metrics = c.metrics()
                    level = int(metrics.get("device.backend_level", -99))
                    if level == 1:
                        break
                time.sleep(0.25)
            assert c.hash() == golden.root_hex(), (
                "HASH diverged from the CPU golden chain under the fault"
            )
            assert level == 1, (
                f"serving backend never landed on the surviving "
                f"single-device rung (backend_level={level})"
            )
            # Still live for normal traffic on the degraded rung.
            c.set("chaos:after", "x")
            golden.insert("chaos:after", "x")
            deadline = time.time() + 30
            while time.time() < deadline:
                if c.hash() == golden.root_hex():
                    break
                time.sleep(0.1)
            assert c.hash() == golden.root_hex()
        assert node.poll() is None, "node process died"
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
