"""Chaos suite: anti-entropy convergence under injected partial failure.

Every scenario drives a real 2-node (or 3-node) sync through the
FaultInjector TCP proxy (merklekv_tpu/testing/faults.py) with a FIXED seed,
so a failure replays bit-identically. The acceptance bar (ISSUE 1): the
nodes converge to identical Merkle roots under chunk drop, delay+reorder,
duplication, truncation, and a peer killed mid-sync — and a mid-sync death
leaves partial repairs applied, checkpoints the remainder, and the next
cycle RESUMES instead of restarting.

The long randomized soak is marked ``slow`` (excluded from tier-1); the
fixed-seed cases here are the tier-1 smoke coverage.
"""

from __future__ import annotations

import time

import pytest

from merklekv_tpu.cluster.retry import Deadline, RetryPolicy
from merklekv_tpu.cluster.sync import SyncManager
from merklekv_tpu.native_bindings import NativeEngine, NativeServer
from merklekv_tpu.testing.faults import FaultInjector, FaultyTransport

# Fast-failing policy for chaos runs: short op timeout so injected stalls
# cost milliseconds, a couple of connect retries, bounded cycle budget.
FAST = RetryPolicy(
    first_delay=0.01,
    max_delay=0.05,
    jitter=0.0,
    attempts=2,
    op_timeout=0.5,
    op_deadline=30.0,
)


def fill(eng, items):
    for k, v in items.items():
        eng.set(k.encode(), v.encode())


def snapshot(eng) -> dict:
    return dict(eng.snapshot())


class ChaosPair:
    """Local engine + remote engine/server, injector in front of remote."""

    def __init__(
        self,
        seed: int,
        divergent: int = 120,
        mget_batch: int = 16,
        hash_page: int = 64,
    ):
        self.local = NativeEngine("mem")
        self.remote = NativeEngine("mem")
        self.srv = NativeServer(self.remote, "127.0.0.1", 0)
        self.srv.start()
        self.inj = FaultInjector("127.0.0.1", self.srv.port, seed=seed)
        self.degraded: list[tuple[str, str]] = []
        self.mgr = SyncManager(
            self.local,
            device="cpu",
            mget_batch=mget_batch,
            retry=FAST,
            hash_page=hash_page,
            on_peer_degraded=lambda p, r: self.degraded.append((p, r)),
        )
        # Local first, remote second: remote writes are newer, so resumed
        # (LWW-conditional) repairs deterministically win.
        fill(self.local, {f"k{i:04d}": "stale" for i in range(divergent // 2)})
        fill(self.remote, {f"k{i:04d}": f"fresh-{i}" for i in range(divergent)})

    @property
    def peer(self) -> str:
        return f"{self.inj.host}:{self.inj.port}"

    def sync_until_converged(self, max_cycles: int = 60) -> int:
        """Run sync cycles through the injector until roots match; returns
        the number of cycles used. Individual cycles are ALLOWED to die —
        that is the point — but the sequence must converge."""
        for cycle in range(1, max_cycles + 1):
            try:
                self.mgr.sync_once(self.inj.host, self.inj.port)
            except Exception:
                pass
            if self.local.merkle_root() == self.remote.merkle_root():
                return cycle
        raise AssertionError(
            f"no convergence in {max_cycles} cycles "
            f"(dropped={self.inj.chunks_dropped} "
            f"dup={self.inj.chunks_duplicated} "
            f"reordered={self.inj.chunks_reordered})"
        )

    def close(self):
        self.mgr.stop()
        self.inj.close()
        self.srv.close()
        self.local.close()
        self.remote.close()


@pytest.fixture
def make_pair():
    pairs = []

    def _make(seed: int, **kw) -> ChaosPair:
        p = ChaosPair(seed, **kw)
        pairs.append(p)
        return p

    yield _make
    for p in pairs:
        p.close()


# --------------------------------------------------------------- fault mix


def test_converges_under_drop(make_pair):
    """30% chunk drop in both directions: cycles die mid-stream, partial
    repairs stick, checkpoints resume — and the pair still converges."""
    p = make_pair(seed=7)
    p.inj.set_faults("both", drop_rate=0.3)
    cycles = p.sync_until_converged()
    assert snapshot(p.local) == snapshot(p.remote)
    assert p.inj.chunks_dropped > 0, "fault never fired; scenario is vacuous"
    # The whole point of resumable sessions: progress survives the faults.
    assert cycles >= 1


def test_converges_under_delay_and_reorder(make_pair):
    p = make_pair(seed=11)
    p.inj.set_faults("both", delay=(0.0, 0.02), reorder_rate=0.3)
    p.sync_until_converged()
    assert snapshot(p.local) == snapshot(p.remote)
    assert p.inj.chunks_reordered > 0, "fault never fired"


def test_converges_under_duplication(make_pair):
    p = make_pair(seed=13)
    p.inj.set_faults("both", dup_rate=0.4)
    p.sync_until_converged()
    assert snapshot(p.local) == snapshot(p.remote)
    assert p.inj.chunks_duplicated > 0, "fault never fired"


def test_converges_under_truncation(make_pair):
    p = make_pair(seed=17)
    p.inj.set_faults("s2c", truncate_rate=0.2)
    p.sync_until_converged()
    assert snapshot(p.local) == snapshot(p.remote)
    assert p.inj.chunks_truncated > 0, "fault never fired"


# ------------------------------------------------- peer death + resumption


def test_peer_death_mid_sync_checkpoints_and_resumes(make_pair):
    """Kill the peer after the 20th applied repair: the applied prefix
    stays, the remainder is checkpointed, the peer is marked degraded,
    and the next cycle RESUMES (fetches only the remainder) rather than
    restarting from scratch."""
    p = make_pair(seed=23, divergent=120, mget_batch=8)
    repairs: list[bytes] = []

    def killer_listener(batch):
        repairs.extend(key for key, _, _ in batch)
        if len(repairs) - len(batch) < 20 <= len(repairs):
            p.inj.kill_peer()

    p.mgr._repair_listener = killer_listener

    with pytest.raises(Exception):
        p.mgr.sync_once(p.inj.host, p.inj.port)

    # Partial repairs stayed applied.
    local_now, remote_now = snapshot(p.local), snapshot(p.remote)
    applied = sum(1 for k, v in remote_now.items() if local_now.get(k) == v)
    assert 20 <= applied < len(remote_now), (applied, len(remote_now))
    # The remainder is checkpointed and the peer marked degraded.
    sess = p.mgr.session_for(p.peer)
    assert sess is not None and len(sess.pending_sets) > 0
    assert any(peer == p.peer for peer, _ in p.degraded)

    # Peer restarts; the next cycle resumes from the checkpoint.
    p.mgr._repair_listener = None
    p.inj.revive()
    report = p.mgr.sync_once(p.inj.host, p.inj.port)
    assert report.resumed is True
    assert any("resuming session" in d for d in report.details)
    # Resume drained the checkpointed remainder and continued the paged
    # walk from the cursor — the already-repaired prefix was NOT refetched.
    assert report.values_fetched >= len(sess.pending_sets)
    assert report.values_fetched <= 120 - applied
    assert p.local.merkle_root() == p.remote.merkle_root()
    assert p.mgr.session_for(p.peer) is None


def test_session_abandoned_after_max_attempts(make_pair):
    """A session that keeps failing is dropped (fresh diff next cycle),
    never resumed forever."""
    from merklekv_tpu.cluster import sync as sync_mod

    p = make_pair(seed=29)
    sess = sync_mod.SyncSession(
        peer=p.peer,
        pending_sets=[(b"k0000", 1)],
        attempts=sync_mod._SESSION_MAX_ATTEMPTS,
    )
    p.mgr._sessions[p.peer] = sess
    report = p.mgr.sync_once(p.inj.host, p.inj.port)
    assert report.resumed is False  # stale session discarded, normal cycle
    assert p.local.merkle_root() == p.remote.merkle_root()


def test_multi_peer_cycle_survives_mid_sync_peer_death(make_pair):
    """sync_multi: one peer dying mid-repair no longer aborts the cycle —
    the other peer's repairs land, the dead peer is checkpointed and
    degraded, and the next cycle resumes it."""
    local = NativeEngine("mem")
    eng_a, eng_b = NativeEngine("mem"), NativeEngine("mem")
    srv_a = NativeServer(eng_a, "127.0.0.1", 0)
    srv_b = NativeServer(eng_b, "127.0.0.1", 0)
    srv_a.start()
    srv_b.start()
    inj_b = FaultInjector("127.0.0.1", srv_b.port, seed=31)
    degraded: list[str] = []
    killed = []

    def listener(batch):
        # First batch of b-key repairs kills peer B mid-stream.
        for key, _, _ in batch:
            if key.startswith(b"b") and not killed:
                killed.append(key)
                inj_b.kill_peer()

    mgr = SyncManager(
        local,
        device="cpu",
        mget_batch=8,
        retry=FAST,
        repair_listener=listener,
        on_peer_degraded=lambda peer, r: degraded.append(peer),
    )
    try:
        fill(eng_a, {f"a{i:03d}": f"va{i}" for i in range(24)})
        fill(eng_b, {f"b{i:03d}": f"vb{i}" for i in range(32)})
        peer_a = f"127.0.0.1:{srv_a.port}"
        peer_b = f"{inj_b.host}:{inj_b.port}"

        report = mgr.sync_multi([peer_a, peer_b])
        # Peer A's repairs all landed despite B dying mid-cycle.
        local_snap = snapshot(local)
        assert all(
            local_snap.get(k) == v for k, v in snapshot(eng_a).items()
        ), "live peer's repairs must not be lost to the dead peer"
        assert peer_b in report.degraded
        assert peer_b in degraded
        sess = mgr.session_for(peer_b)
        assert sess is not None and len(sess.pending_sets) > 0

        # B restarts: next cycle resumes its checkpoint and converges.
        mgr._repair_listener = None
        inj_b.revive()
        report2 = mgr.sync_multi([peer_a, peer_b])
        assert peer_b in report2.resumed_peers
        local_snap = snapshot(local)
        for k, v in snapshot(eng_b).items():
            assert local_snap.get(k) == v
    finally:
        mgr.stop()
        inj_b.close()
        srv_a.close()
        srv_b.close()
        local.close()
        eng_a.close()
        eng_b.close()


# ---------------------------------------------------- deadline checkpoints


def test_expired_deadline_checkpoints_without_error(make_pair):
    """An exhausted per-peer cycle budget checkpoints the remainder and
    returns cleanly; the next cycle resumes."""
    p = make_pair(seed=37, divergent=80, mget_batch=8)
    # A deadline that expires immediately: every batch checkpoints.
    expired = Deadline(0.0)
    time.sleep(0.001)
    from merklekv_tpu.client import MerkleKVClient
    from merklekv_tpu.cluster.sync import SyncReport

    report = SyncReport(peer=p.peer)

    with MerkleKVClient(p.inj.host, p.inj.port, timeout=1.0) as c:
        pairs = [(f"k{i:04d}".encode(), 1) for i in range(40)]
        p.mgr._repair_sets_resumable(c, p.peer, pairs, report, expired, lww=True)
    sess = p.mgr.session_for(p.peer)
    assert sess is not None and len(sess.pending_sets) == 40
    assert any("deadline expired" in d for d in report.details)
    # Next normal cycle (fresh deadline) drains the session and converges.
    rep = p.mgr.sync_once(p.inj.host, p.inj.port)
    assert rep.resumed is True
    assert p.local.merkle_root() == p.remote.merkle_root()


# -------------------------------------------------- device-path degradation


def test_device_failure_falls_back_to_cpu(make_pair, monkeypatch):
    """A TPU/Pallas init failure degrades to host hashing with a one-time
    warning instead of killing every cycle."""
    import warnings

    from merklekv_tpu.cluster import sync as sync_mod
    from merklekv_tpu.utils import jaxenv

    # Isolate the sticky global so this test cannot leak into others.
    monkeypatch.setattr(jaxenv, "_device_fallback", False)

    def boom(items):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(sync_mod, "_leaf_map_device", boom)
    p = make_pair(seed=41, divergent=40)
    p.mgr._device = "tpu"  # force the device path
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        p.mgr.sync_once(p.inj.host, p.inj.port)
        p.mgr.sync_once(p.inj.host, p.inj.port)
    assert p.local.merkle_root() == p.remote.merkle_root()
    assert jaxenv.device_failed()
    relevant = [w for w in caught if "falling back" in str(w.message)]
    assert len(relevant) == 1, "device-failure warning must fire exactly once"


# -------------------------------------------------- message-level transport


def test_faulty_transport_deterministic_faults():
    """FaultyTransport: whole-message drop/dup/reorder under a fixed seed,
    replayed identically."""

    class Recorder:
        def __init__(self):
            self.messages = []

        def publish(self, topic, payload):
            self.messages.append(payload)

        def subscribe(self, *a):
            pass

        def unsubscribe(self, *a):
            pass

        def close(self):
            pass

    def run(seed):
        rec = Recorder()
        ft = FaultyTransport(
            rec, seed=seed, drop_rate=0.2, dup_rate=0.2, reorder_rate=0.2
        )
        for i in range(50):
            ft.publish("t", b"m%d" % i)
        ft.flush_held()
        return rec.messages, (ft.dropped, ft.duplicated, ft.reordered)

    msgs1, stats1 = run(99)
    msgs2, stats2 = run(99)
    assert msgs1 == msgs2, "same seed must replay the same schedule"
    assert stats1 == stats2
    dropped, duplicated, reordered = stats1
    assert dropped > 0 and duplicated > 0 and reordered > 0
    # Every non-dropped message is delivered (dups add, drops remove).
    assert len(msgs1) == 50 - dropped + duplicated


def test_replication_converges_through_faulty_transport():
    """Replication events through a lossy/reordering/duplicating fabric:
    op-id dedupe + LWW absorb the faults, anti-entropy repairs the drops,
    and the nodes converge."""
    from merklekv_tpu.cluster.replicator import Replicator
    from merklekv_tpu.cluster.transport import InProcessBus

    bus = InProcessBus()
    engines, servers, reps = [], [], []
    try:
        for i in range(2):
            eng = NativeEngine("mem")
            srv = NativeServer(eng, "127.0.0.1", 0)
            srv.start()
            ft = FaultyTransport(
                bus, seed=50 + i, drop_rate=0.3, dup_rate=0.3,
                reorder_rate=0.2,
            )
            rep = Replicator(
                eng, srv, ft, topic_prefix="chaos", node_id=f"n{i}"
            )
            rep.start()
            engines.append(eng)
            servers.append(srv)
            reps.append(rep)

        from merklekv_tpu.client import MerkleKVClient

        with MerkleKVClient("127.0.0.1", servers[0].port) as c0, \
                MerkleKVClient("127.0.0.1", servers[1].port) as c1:
            for i in range(40):
                (c0 if i % 2 == 0 else c1).set(f"fx{i:03d}", f"v{i}")
        for rep in reps:
            rep.flush()
        time.sleep(0.3)  # let the bus dispatcher drain

        # Anti-entropy backstop repairs whatever the faults ate.
        mgr = SyncManager(engines[0], device="cpu", retry=FAST)
        for _ in range(5):
            try:
                mgr.sync_once("127.0.0.1", servers[1].port)
            except Exception:
                pass
            if engines[0].merkle_root() == engines[1].merkle_root():
                break
        # One-way sync converges node0 to node1; finish with reverse pass.
        mgr1 = SyncManager(engines[1], device="cpu", retry=FAST)
        mgr1.sync_once("127.0.0.1", servers[0].port)
        assert engines[0].merkle_root() == engines[1].merkle_root()
        assert snapshot(engines[0]) == snapshot(engines[1])
    finally:
        for rep in reps:
            rep.stop()
        for srv in servers:
            srv.close()
        for eng in engines:
            eng.close()
        bus.close()


# ------------------------------------------------------------ slow soak


@pytest.mark.slow
@pytest.mark.parametrize("seed", [101, 202, 303, 404, 505])
def test_soak_full_fault_mix(make_pair, seed):
    """Randomized (but seeded) soak: every fault class at once, larger
    keyspace, must still converge. Excluded from tier-1 via ``slow``."""
    p = make_pair(seed=seed, divergent=400, mget_batch=32)
    p.inj.set_faults(
        "both",
        drop_rate=0.15,
        dup_rate=0.15,
        reorder_rate=0.15,
        delay=(0.0, 0.01),
    )
    p.sync_until_converged(max_cycles=120)
    assert snapshot(p.local) == snapshot(p.remote)


def test_bandwidth_throttle_paces_stream():
    """The token-bucket bandwidth fault: a capped direction delivers at
    most rate bytes/s (plus one burst allowance) — the slow-WAN shape the
    snapshot-shipping resume tests lean on."""
    import socket as _socket
    import threading as _threading

    sink = _socket.socket()
    sink.bind(("127.0.0.1", 0))
    sink.listen(1)
    received = {"n": 0}
    done = _threading.Event()

    def drain():
        conn, _ = sink.accept()
        try:
            while True:
                # The proxy hard-closes with RST once the client side goes
                # away; everything forwarded before that still counts.
                chunk = conn.recv(65536)
                if not chunk:
                    break
                received["n"] += len(chunk)
        except OSError:
            pass
        conn.close()
        done.set()

    _threading.Thread(target=drain, daemon=True).start()
    inj = FaultInjector("127.0.0.1", sink.getsockname()[1], seed=5)
    inj.set_faults("c2s", bandwidth_bytes_per_s=32 * 1024)
    try:
        payload = b"x" * (96 * 1024)
        t0 = time.perf_counter()
        s = _socket.create_connection((inj.host, inj.port))
        s.sendall(payload)
        # Half-close: EOF reaches the proxy only after it has drained (and
        # throttled) everything we sent; a full close could RST the stream
        # out from under the pacing loop.
        s.shutdown(_socket.SHUT_WR)
        assert done.wait(timeout=20)
        elapsed = time.perf_counter() - t0
        s.close()
        assert received["n"] == len(payload)
        # 96 KiB at 32 KiB/s with a 32 KiB burst: >= ~2 s on the wire.
        assert elapsed >= 1.5, f"throttle did not pace: {elapsed:.2f}s"
        assert inj.chunks_throttled > 0
    finally:
        inj.close()
        sink.close()
