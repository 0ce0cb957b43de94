"""Rebalance chaos through REAL processes (the CI rebalance chaos
smoke): a storage-backed 2-partition cluster plus one reserve, spawned
as real ``python -m merklekv_tpu`` nodes over a spawned broker, a live
2->3 ``REBALANCE SPLIT``, and a kill -9 (no shutdown path, no flush) of
EACH side mid-transfer:

- joiner killed mid-fetch -> the donor rolls the session back (epoch
  stays at 1, donor root bit-identical to pre-split — nothing lost,
  nothing dropped), and the SAME donor then completes a clean split
  against a respawned reserve;
- donor killed mid-fetch -> the joiner aborts back to reserve on its
  own, the respawned donor recovers its full keyspace from the WAL at
  the old epoch (root bit-identical), the offline blackbox analyzer
  exits 0 on the killed donor's flight spill, and a re-issued split
  commits — while a write storm against the OTHER partition rides
  through the whole drill with zero client-visible errors.

The transfer window is held open deterministically via the
MERKLEKV_REBALANCE_CHUNK_BYTES / MERKLEKV_REBALANCE_FETCH_PAUSE_S
chaos knobs (rebalance.py) so "mid-transfer" means mid-stream, not a
lucky race.
"""

import os
import socket
import subprocess
import sys
import threading
import time
import uuid

import pytest

from merklekv_tpu.client import MerkleKVClient, PartitionedClient
from merklekv_tpu.cluster.partmap import hash_of_key
from merklekv_tpu.testing.faults import PeerProcessKiller

pytestmark = pytest.mark.integration

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Hold the snapshot stream open ~2 s (tiny chunks + per-chunk pause) so
# the kill -9 lands mid-stream; shrink the joiner's donor-loss resolve
# budget so the drill doesn't wait out the production default.
CHAOS_ENV = {
    "MERKLEKV_REBALANCE_CHUNK_BYTES": "1024",
    "MERKLEKV_REBALANCE_FETCH_PAUSE_S": "0.05",
    "MERKLEKV_REBALANCE_RESOLVE_S": "8",
}


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


class ProcCluster:
    """Broker + donor (p0) + sibling (p1) + one reserve, all real
    processes with durable storage, chaos knobs armed."""

    def __init__(self, tmp_path):
        self.tmp = tmp_path
        self.topic = f"rbproc-{uuid.uuid4().hex[:8]}"
        self.ports = _free_ports(3)
        self.addr = [f"127.0.0.1:{p}" for p in self.ports]
        self.spec = f"0={self.addr[0]};1={self.addr[1]}"
        self.procs = {}
        self.broker = self._spawn(["-m", "merklekv_tpu.broker",
                                   "--port", "0"])
        self.broker_port = self._port_from(self.broker)
        for i in range(3):
            self.spawn_node(i)

    def _spawn(self, args):
        env = dict(os.environ, PYTHONPATH=REPO,
                   JAX_PLATFORMS="cpu", **CHAOS_ENV)
        return subprocess.Popen(
            [sys.executable, *args],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )

    def _port_from(self, proc):
        line = proc.stdout.readline()
        assert "listening on" in line, f"unexpected startup line: {line!r}"
        port = int(line.rsplit(":", 1)[1].split()[0])
        # Drain the rest so a chatty node never blocks on a full pipe.
        threading.Thread(
            target=lambda: [None for _ in proc.stdout], daemon=True
        ).start()
        return port

    def _wait_port(self, port, timeout=30):
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                socket.create_connection(
                    ("127.0.0.1", port), timeout=1
                ).close()
                return
            except OSError:
                time.sleep(0.05)
        raise TimeoutError(f"port {port} never came up")

    def node_toml(self, i):
        cluster = (
            f'[cluster]\npartitions = 2\npartition_id = {i}\n'
            f'partition_map = "{self.spec}"\n'
            if i < 2
            else ""
        )
        cfg = self.tmp / f"node-{i}.toml"
        cfg.write_text(
            f"""
host = "127.0.0.1"
port = {self.ports[i]}
engine = "mem"
storage_path = "{self.tmp}/n{i}"
{cluster}
[storage]
enabled = true
merkle_engine = "cpu"

[replication]
enabled = {"true" if i < 2 else "false"}
mqtt_broker = "127.0.0.1"
mqtt_port = {self.broker_port}
topic_prefix = "{self.topic}"

[anti_entropy]
engine = "cpu"
interval_seconds = 3600

[observability]
flight_spill_s = 0.5
"""
        )
        return cfg

    def spawn_node(self, i):
        proc = self._spawn(["-m", "merklekv_tpu", "--config",
                            str(self.node_toml(i))])
        self.procs[i] = proc
        self._wait_port(self._port_from(proc))
        return proc

    def kill9(self, i):
        killer = PeerProcessKiller(self.procs.pop(i))
        killer.kill_now()
        assert killer.killed

    def client(self, i, timeout=10):
        return MerkleKVClient("127.0.0.1", self.ports[i], timeout=timeout)

    def rebal_state(self, i):
        with self.client(i) as c:
            return c.rebalance("STATUS").split(" ")[1]

    def split(self, joiner=2):
        with self.client(0) as c:
            epoch = c.partition_map().epoch
            resp = c.rebalance(f"SPLIT 0 {epoch} {self.addr[joiner]}")
        assert resp.startswith("OK"), resp
        return resp

    def wait_state(self, i, want, timeout=60):
        deadline = time.time() + timeout
        state = None
        while time.time() < deadline:
            try:
                state = self.rebal_state(i)
            except OSError:
                state = None
            if state in want:
                return state
            time.sleep(0.02)
        raise TimeoutError(f"node {i} never reached {want} (last {state})")

    def close(self):
        for proc in list(self.procs.values()) + [self.broker]:
            proc.terminate()
        for proc in list(self.procs.values()) + [self.broker]:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


@pytest.fixture
def cluster(tmp_path):
    cl = ProcCluster(tmp_path)
    try:
        yield cl
    finally:
        cl.close()


def _seed(cl, n=3000):
    pc = PartitionedClient([cl.addr[0]], timeout=10).connect()
    for i in range(n):
        pc.set(f"rb:{i:06d}", f"v-{i}")
    pc.close()
    return {f"rb:{i:06d}": f"v-{i}" for i in range(n)}


def _root_of(cl, i, pid):
    with cl.client(i) as c:
        c.partition_id = pid  # pt=-addressed: MOVED if misrouted
        return c.hash()


def _dbsize_of(cl, i):
    with cl.client(i) as c:
        return c.dbsize()


def _readback_all(cl, kv):
    pc = PartitionedClient([cl.addr[1]], timeout=10).connect()
    try:
        missing = [k for k, v in kv.items() if pc.get(k) != v]
        assert not missing, f"{len(missing)} keys lost, e.g. {missing[:3]}"
    finally:
        pc.close()


def test_kill9_joiner_mid_transfer_then_clean_split(cluster):
    kv = _seed(cluster)
    root0 = _root_of(cluster, 0, 0)
    p0_before = _dbsize_of(cluster, 0)

    # Kill the joiner mid-stream: wait until it is actively fetching
    # (join_fetch), let a few chunks land, then SIGKILL.
    cluster.split(joiner=2)
    cluster.wait_state(2, {"join_fetch"}, timeout=30)
    time.sleep(0.3)
    cluster.kill9(2)

    # The donor declares the joiner dead and rolls the whole session
    # back: old epoch, bit-identical root, every key still served.
    cluster.wait_state(0, {"failed"}, timeout=60)
    with cluster.client(0) as c:
        m = c.partition_map()
    assert (m.epoch, m.count) == (1, 2)
    assert _root_of(cluster, 0, 0) == root0
    _readback_all(cluster, kv)

    # The SAME donor completes a clean split against a respawned
    # reserve — a failed rebalance must not poison the next one.
    cluster.spawn_node(2)
    cluster.split(joiner=2)
    cluster.wait_state(0, {"done"}, timeout=120)
    with cluster.client(0) as c:
        m = c.partition_map()
    assert (m.epoch, m.count) == (2, 3)
    moved = _dbsize_of(cluster, 2)
    assert moved > 0
    assert _dbsize_of(cluster, 0) + moved == p0_before
    _readback_all(cluster, kv)


def test_kill9_donor_mid_transfer_joiner_aborts_blackbox_parses(cluster):
    kv = _seed(cluster)
    root1 = _root_of(cluster, 1, 1)

    # A storm against partition 1 rides through the whole drill: the
    # donor's death mid-rebalance must not touch the other partition.
    p1_keys = [
        k for k in kv
        if hash_of_key(k.encode()) % 2 == 1
    ][:200]
    assert p1_keys
    errors = []
    stop = threading.Event()

    def storm():
        pc = PartitionedClient([cluster.addr[1]], timeout=10).connect()
        try:
            i = 0
            while not stop.is_set():
                pc.set(p1_keys[i % len(p1_keys)], kv[p1_keys[i % len(p1_keys)]])
                i += 1
                time.sleep(0.002)
        except BaseException as e:
            errors.append(e)
        finally:
            pc.close()

    t = threading.Thread(target=storm, daemon=True)
    t.start()
    try:
        cluster.split(joiner=2)
        cluster.wait_state(2, {"join_fetch"}, timeout=30)
        time.sleep(0.3)
        cluster.kill9(0)

        # The joiner loses its donor mid-stream, aborts on its own, and
        # returns to reserve duty holding nothing.
        cluster.wait_state(2, {"join_aborted"}, timeout=60)
        assert _dbsize_of(cluster, 2) == 0

        # The kill -9'd donor left a parseable black box behind.
        flight = os.path.join(
            str(cluster.tmp), "n0", f"node-{cluster.ports[0]}", "flight"
        )
        rc = subprocess.run(
            [sys.executable, "-m", "merklekv_tpu", "blackbox", flight],
            env=dict(os.environ, PYTHONPATH=REPO,
                     JAX_PLATFORMS="cpu"),
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        ).returncode
        assert rc == 0, f"blackbox analyzer failed on {flight}"

        # Respawn the donor: WAL recovery resurrects the FULL keyspace
        # at the old epoch (the commit point was never reached).
        cluster.spawn_node(0)
        with cluster.client(0) as c:
            m = c.partition_map()
        assert (m.epoch, m.count) == (1, 2)
        _readback_all(cluster, kv)

        # And the cluster is not poisoned: a re-issued split commits.
        cluster.wait_state(2, {"join_aborted", "idle"}, timeout=10)
        cluster.split(joiner=2)
        cluster.wait_state(0, {"done"}, timeout=120)
        with cluster.client(0) as c:
            m = c.partition_map()
        assert (m.epoch, m.count) == (2, 3)
        _readback_all(cluster, kv)
    finally:
        stop.set()
        t.join(timeout=10)

    assert not errors, f"storm saw errors: {errors[:3]!r}"
    assert _root_of(cluster, 1, 1) == root1  # p1 untouched by the drill
