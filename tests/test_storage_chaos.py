"""Crash chaos for the durable subsystem: SIGKILL mid-write-burst, restart
from disk, verify the recovered root against the on-disk truth, and let
anti-entropy re-converge a cluster around the crash.

The acceptance shape from the ISSUE: PeerProcessKiller kills a node whose
WAL is mid-burst; the node restarts from snapshot+WAL; the recovered root
hash equals what `walcheck` computes offline from the surviving bytes; and
a 2-node cluster converges again without manual intervention.

Fast fixed cases stay in tier-1; the repeated kill/restart soak is `slow`.
"""

import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from merklekv_tpu.client import MerkleKVClient
from merklekv_tpu.storage import node_data_dir
from merklekv_tpu.storage import wal as walmod
from merklekv_tpu.storage.walcheck import check_dir, replay_root_hex
from merklekv_tpu.testing.faults import PeerProcessKiller

pytestmark = pytest.mark.integration

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(args):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, *args],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        sk = socket.socket()
        sk.bind(("127.0.0.1", 0))
        ports.append(sk.getsockname()[1])
        socks.append(sk)
    for sk in socks:
        sk.close()
    return ports


def _await_ready(proc, port, timeout=20):
    line = proc.stdout.readline()
    assert "listening on" in line, f"unexpected startup line: {line!r}"
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            return
        except OSError:
            time.sleep(0.05)
    raise TimeoutError(f"port {port} never came up")


def _reap(procs):
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()


def _storage_toml(path, port, data_dir, extra=""):
    path.write_text(
        f"""
host = "127.0.0.1"
port = {port}
engine = "mem"
storage_path = "{data_dir}"

[storage]
enabled = true
fsync = "always"
merkle_engine = "cpu"
{extra}
"""
    )
    return str(path)


def _wal_payload_bytes(node_dir):
    """Bytes of framed records on disk (beyond per-segment magic)."""
    total = 0
    for _, p in walmod.list_segments(node_dir):
        total += max(0, os.path.getsize(p) - len(walmod.SEGMENT_MAGIC))
    return total


def _burst_writer(port, key_fmt, stop_on_error=True):
    """Background writer hammering SET on one connection; returns a dict
    whose 'acked' field grows with every acknowledged write."""
    state = {"acked": 0, "done": threading.Event()}

    def run():
        try:
            with MerkleKVClient("127.0.0.1", port) as c:
                for i in range(200_000):
                    c.set(key_fmt % i, f"val-{i}")
                    state["acked"] += 1
        except Exception:
            pass  # the connection dies at the kill — expected
        finally:
            state["done"].set()

    threading.Thread(target=run, daemon=True).start()
    return state


def test_kill9_midburst_recovered_root_matches_disk(tmp_path):
    """Tier-1 acceptance core: SIGKILL mid-burst, walcheck the surviving
    bytes offline, restart, and the served HASH equals the offline root —
    recovery restored exactly the durable prefix, verified via the stamped
    snapshot + WAL replay, nothing invented and nothing lost."""
    (port,) = _free_ports(1)
    data = tmp_path / "data"
    cfg = _storage_toml(tmp_path / "node.toml", port, data)
    node_dir = node_data_dir(str(data), port)

    p = _spawn(["-m", "merklekv_tpu", "--config", cfg])
    try:
        _await_ready(p, port)
        state = _burst_writer(port, "cr:%06d")
        killer = PeerProcessKiller(p)
        # Kill only once a healthy chunk of the burst is framed on disk, so
        # the recovery below demonstrably restores a non-trivial prefix.
        killed = killer.kill_when(
            lambda: state["acked"] >= 200 and _wal_payload_bytes(node_dir) > 2048,
            timeout=30,
        )
        state["done"].wait(timeout=10)
        assert killed, f"only {state['acked']} acks before deadline"
        acked = state["acked"]
    finally:
        _reap([p])

    # Offline truth from the surviving bytes (torn tail allowed: that is
    # the crash signature, not corruption).
    report = check_dir(node_dir)
    assert not report["errors"], report["errors"]
    expected_root = report["replay_root"]
    durable_keys = report["live_keys"]
    assert durable_keys > 0

    p2 = _spawn(["-m", "merklekv_tpu", "--config", cfg])
    try:
        _await_ready(p2, port)
        with MerkleKVClient("127.0.0.1", port) as c:
            assert c.hash() == expected_root
            keys = c.scan("cr:")
            assert len(keys) == durable_keys
            # Write-order contiguity: the WAL drains the event queue in seq
            # order, so the durable set is exactly a prefix of the burst.
            idxs = sorted(int(k.split(":")[1]) for k in keys)
            assert idxs == list(range(len(idxs)))
            assert len(idxs) <= acked + 1
            # The recovered node keeps serving writes durably.
            c.set("post-recovery", "alive")
            assert c.get("post-recovery") == "alive"
    finally:
        _reap([p2])


def test_kill9_recovery_then_anti_entropy_reconverges(tmp_path):
    """The full acceptance loop: kill -9 one node of a 2-node anti-entropy
    pair mid-burst, restart it from disk, and the cluster converges to one
    root without manual intervention — the durable prefix survives the
    crash locally, the lost tail plus the peer's writes arrive via sync."""
    port_a, port_b = _free_ports(2)
    data = tmp_path / "data"
    # multi_peer: the fused LWW arbitration mode — pairwise mode is strict
    # local := peer and would discard whichever side's disjoint writes the
    # last cycle overwrote.
    ae = """
[anti_entropy]
enabled = true
interval_seconds = 0.3
engine = "cpu"
multi_peer = true
peers = ["127.0.0.1:%d"]
"""
    cfg_a = _storage_toml(
        tmp_path / "a.toml", port_a, data, extra=ae % port_b
    )
    cfg_b = _storage_toml(
        tmp_path / "b.toml", port_b, data, extra=ae % port_a
    )

    pa = _spawn(["-m", "merklekv_tpu", "--config", cfg_a])
    pb = _spawn(["-m", "merklekv_tpu", "--config", cfg_b])
    pa2 = None
    try:
        _await_ready(pa, port_a)
        _await_ready(pb, port_b)

        state = _burst_writer(port_a, "burst:%06d")
        killer = PeerProcessKiller(pa)
        node_a_dir = node_data_dir(str(data), port_a)
        killed = killer.kill_when(
            lambda: state["acked"] >= 150
            and _wal_payload_bytes(node_a_dir) > 1024,
            timeout=30,
        )
        state["done"].wait(timeout=10)
        assert killed

        # Disjoint writes land on B while A is down.
        with MerkleKVClient("127.0.0.1", port_b) as cb:
            for i in range(20):
                cb.set(f"bonly:{i:03d}", f"bv-{i}")

        pa2 = _spawn(["-m", "merklekv_tpu", "--config", cfg_a])
        _await_ready(pa2, port_a)

        with MerkleKVClient("127.0.0.1", port_a) as ca, MerkleKVClient(
            "127.0.0.1", port_b
        ) as cb:
            # A restarted from disk with a verified prefix of the burst.
            recovered = len(ca.scan("burst:"))
            assert recovered > 0
            deadline = time.time() + 30
            while time.time() < deadline:
                if (
                    ca.hash() == cb.hash()
                    and ca.get("bonly:000") is not None
                ):
                    break
                time.sleep(0.2)
            assert ca.hash() == cb.hash(), "cluster failed to re-converge"
            # Both directions repaired: B holds A's durable burst prefix,
            # A holds B's solo writes.
            assert ca.get("bonly:019") == "bv-19"
            assert len(cb.scan("burst:")) >= recovered
    finally:
        _reap([p for p in (pa, pb, pa2) if p is not None])


@pytest.mark.slow
def test_soak_repeated_kill_restart_cycles(tmp_path):
    """Crash-recovery soak: several kill -9 / restart cycles against one
    data dir, each mid-burst. Every recovery must verify (no walcheck
    errors) and serve exactly the on-disk root, with the keyspace growing
    monotonically across cycles."""
    (port,) = _free_ports(1)
    data = tmp_path / "data"
    cfg = _storage_toml(
        tmp_path / "node.toml",
        port,
        data,
        # Tighter segments + trigger so the soak exercises rotation and
        # background compaction under crash pressure too.
        extra="segment_bytes = 8192\ncompact_trigger_bytes = 32768\n",
    )
    node_dir = node_data_dir(str(data), port)

    prev_keys = 0
    for cycle in range(4):
        p = _spawn(["-m", "merklekv_tpu", "--config", cfg])
        try:
            _await_ready(p, port)
            state = _burst_writer(port, f"c{cycle}:%06d")
            killer = PeerProcessKiller(p)
            baseline = _wal_payload_bytes(node_dir)
            killed = killer.kill_when(
                lambda: state["acked"] >= 150
                and _wal_payload_bytes(node_dir) > baseline + 1024,
                timeout=30,
            )
            state["done"].wait(timeout=10)
            assert killed, f"cycle {cycle}: no kill"
        finally:
            _reap([p])

        report = check_dir(node_dir)
        assert not report["errors"], (cycle, report["errors"])
        assert report["live_keys"] > prev_keys
        prev_keys = report["live_keys"]
        expected_root = report["replay_root"]

        p2 = _spawn(["-m", "merklekv_tpu", "--config", cfg])
        try:
            _await_ready(p2, port)
            with MerkleKVClient("127.0.0.1", port) as c:
                assert c.hash() == expected_root, f"cycle {cycle}"
                assert c.dbsize() == prev_keys
        finally:
            _reap([p2])
    assert replay_root_hex(node_dir) is not None
