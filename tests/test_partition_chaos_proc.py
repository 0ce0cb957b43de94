"""Partition chaos through REAL processes (the CI chaos step): a
2-partition x 2-replica cluster of spawned ``python -m merklekv_tpu``
nodes over a spawned broker, a write storm driven through the smart
partitioned client, and a kill -9 (PeerProcessKiller — no shutdown path,
no flush) of one replica in EVERY partition mid-storm. The storm must
ride through on the surviving replicas, the survivors must stay live,
and the respawned replicas must reconverge each partition to a
bit-identical per-partition root.
"""

import os
import socket
import subprocess
import sys
import time
import uuid

import pytest

from merklekv_tpu.client import MerkleKVClient, PartitionedClient
from merklekv_tpu.testing.faults import PeerProcessKiller

pytestmark = pytest.mark.integration

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P, R = 2, 2


def _spawn(args):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, *args],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )


def _port_from(proc) -> int:
    line = proc.stdout.readline()
    assert "listening on" in line, f"unexpected startup line: {line!r}"
    return int(line.rsplit(":", 1)[1].split()[0])


def _wait_port(port, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            return
        except OSError:
            time.sleep(0.05)
    raise TimeoutError(f"port {port} never came up")


def _free_ports(n):
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def test_kill_one_replica_per_partition_real_processes(tmp_path):
    ports = _free_ports(P * R)
    addr = [
        [f"127.0.0.1:{ports[p * R + r]}" for r in range(R)]
        for p in range(P)
    ]
    spec = ";".join(f"{p}=" + ",".join(addr[p]) for p in range(P))
    topic = f"pchaos-{uuid.uuid4().hex[:8]}"
    procs = {}
    broker = _spawn(["-m", "merklekv_tpu.broker", "--port", "0"])
    broker_port = _port_from(broker)

    def node_toml(p, r):
        cfg = tmp_path / f"node-{p}-{r}.toml"
        cfg.write_text(
            f"""
host = "127.0.0.1"
port = {ports[p * R + r]}
engine = "mem"

[cluster]
partitions = {P}
partition_id = {p}
partition_map = "{spec}"

[replication]
enabled = true
mqtt_broker = "127.0.0.1"
mqtt_port = {broker_port}
topic_prefix = "{topic}"

[anti_entropy]
engine = "cpu"
"""
        )
        return cfg

    def spawn_node(p, r):
        proc = _spawn(["-m", "merklekv_tpu", "--config",
                       str(node_toml(p, r))])
        procs[(p, r)] = proc
        port = _port_from(proc)
        _wait_port(port)
        return proc

    try:
        for p in range(P):
            for r in range(R):
                spawn_node(p, r)

        def root_of(p, r):
            host, _, port = addr[p][r].rpartition(":")
            with MerkleKVClient(host, int(port), timeout=5) as c:
                c.partition_id = p  # pt=-addressed: MOVED if misrouted
                return c.hash()

        def metrics_of(p, r):
            host, _, port = addr[p][r].rpartition(":")
            with MerkleKVClient(host, int(port), timeout=5) as c:
                return c.metrics()

        pc = PartitionedClient([addr[0][0]], timeout=5).connect()
        assert pc.map.count == P

        # Seed + wait for in-partition replication to converge, so the
        # killed replicas die holding real state.
        for i in range(120):
            pc.set(f"seed:{i:04d}", f"s{i}")
        deadline = time.time() + 30
        for p in range(P):
            while time.time() < deadline:
                if root_of(p, 0) == root_of(p, 1):
                    break
                time.sleep(0.1)
            assert root_of(p, 0) == root_of(p, 1), (
                f"partition {p} never converged pre-kill"
            )

        # The storm + the kill wave: SIGKILL replica 1 of EVERY partition
        # while writes keep flowing through the smart client (it rotates
        # to the surviving sibling on connection failure).
        killed = {
            p: PeerProcessKiller(procs.pop((p, 1))) for p in range(P)
        }
        storm_n = 300
        for i in range(storm_n):
            pc.set(f"storm:{i:04d}", f"w{i}")
            if i == 60:
                for p in range(P):
                    killed[p].kill_now()
        for p in range(P):
            assert killed[p].killed
        # Survivors never left live while their sibling was dead.
        for p in range(P):
            m = metrics_of(p, 0)
            assert m.get("partition.state") == "0", (
                f"survivor of partition {p} degraded: {m.get('partition.state')}"
            )
            assert m.get("partition.id") == str(p)
        # Every storm key is readable through the surviving replicas.
        assert all(
            pc.get(f"storm:{i:04d}") == f"w{i}" for i in range(storm_n)
        )

        # Respawn the killed replicas (fresh empty engines — a crashed
        # host came back wiped) and repair each partition from its
        # surviving sibling with one SYNC; roots must land bit-identical.
        for p in range(P):
            spawn_node(p, 1)
        for p in range(P):
            h0, _, p0 = addr[p][0].rpartition(":")
            h1, _, p1 = addr[p][1].rpartition(":")
            with MerkleKVClient(h1, int(p1), timeout=30) as c:
                assert c.sync_with(h0, int(p0))
        roots = {}
        for p in range(P):
            assert root_of(p, 0) == root_of(p, 1), (
                f"partition {p} did not reconverge after respawn"
            )
            roots[p] = root_of(p, 0)
        assert len(set(roots.values())) == P  # disjoint keyspaces
        pc.close()
    finally:
        for proc in list(procs.values()) + [broker]:
            proc.terminate()
        for proc in list(procs.values()) + [broker]:
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
