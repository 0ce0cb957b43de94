"""Subtree-bisection anti-entropy: the O(divergence·log n) wire-byte walk.

The reference *documents* a top-down hash-comparison walk
(/root/reference/README.md:310-372) but ships full snapshot transfer; our
hash-first mode still shipped the whole leaf-hash list (O(n·32B)) whenever
roots differed. The bisection walk (TREELEVEL descent + range-bounded
HASHPAGE repair) makes wire bytes scale with divergence·log n:

- walk parity: converged roots bit-identical across the CPU golden tree,
  the device-resident tree, the native host tree, and both peers;
- wire-byte accounting: 1 divergent key in a >= 1M-key keyspace syncs with
  a few KB on the wire (hash-first would ship ~32 MB of digests);
- fault tolerance: a stream killed mid-walk checkpoints (cursor, walk) into
  the SyncSession and the next cycle RESUMES the walk;
- degradation: peers without TREELEVEL, empty peers, and keyspace churn all
  fall back to the paged hash scan.
"""

from __future__ import annotations

import time

import pytest

from merklekv_tpu.client import MerkleKVClient, ProtocolError
from merklekv_tpu.cluster.retry import RetryPolicy
from merklekv_tpu.cluster.sync import SyncManager
from merklekv_tpu.native_bindings import NativeEngine, NativeServer


@pytest.fixture
def two_nodes():
    nodes = []
    for _ in range(2):
        eng = NativeEngine("mem")
        srv = NativeServer(eng, "127.0.0.1", 0)
        srv.start()
        nodes.append((eng, srv))
    yield nodes
    for eng, srv in nodes:
        srv.close()
        eng.close()


def fill(eng, items):
    for k, v in items.items():
        eng.set(k.encode(), v.encode())


# ------------------------------------------------------------ wire verbs


def test_treelevel_serves_reference_levels(two_nodes):
    """TREELEVEL rows are bit-identical to the CPU golden tree's levels
    (including the odd-promotion spine) and carry the live leaf count."""
    from merklekv_tpu.merkle.cpu import build_levels
    from merklekv_tpu.merkle.encoding import leaf_hash

    (_, _), (eng, srv) = two_nodes
    items = {f"tl{i:03d}": f"v{i}" for i in range(100)}
    fill(eng, items)
    gold = build_levels(
        [leaf_hash(k, v) for k, v in sorted(items.items())]
    )
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        # Zero-width probe: capability check + leaf count, no rows.
        rows, n = c.tree_level(0, 0, 0)
        assert rows == [] and n == 100
        for lvl, level_nodes in enumerate(gold):
            rows, n = c.tree_level(lvl, 0, 10**6)  # hi clamps to the level
            assert n == 100
            assert [i for i, _ in rows] == list(range(len(level_nodes)))
            assert [bytes.fromhex(h) for _, h in rows] == level_nodes
        # Past the top level: no rows, but the leaf count still answers.
        rows, n = c.tree_level(len(gold) + 3, 0, 10)
        assert rows == [] and n == 100
        # The served root equals HASH.
        rows, _ = c.tree_level(len(gold) - 1, 0, 1)
        assert rows[0][1] == c.hash()


def test_treelevel_requires_arguments(two_nodes):
    (_, _), (_, srv) = two_nodes
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        assert c._request("TREELEVEL").startswith("ERROR")
        assert c._request("TREELEVEL 0 5 2").startswith("ERROR")
        assert c._request("TREELEVEL -1 0 2").startswith("ERROR")


def test_hashpage_upto_bounds_the_page(two_nodes):
    """Range-bounded HASHPAGE: rows stop strictly below the bound, a short
    page means the RANGE (not the keyspace) is exhausted, and tombstones
    inside the range still ride along."""
    (_, _), (eng, srv) = two_nodes
    fill(eng, {f"hp{i:02d}": "v" for i in range(20)})
    eng.delete(b"hp07")
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        rows, done = c.leaf_hashes_page(100, "hp04", upto="hp09")
        assert [r[0] for r in rows] == ["hp05", "hp06", "hp07", "hp08"]
        assert rows[2][1] is None  # tombstone row in-range
        assert done  # range exhausted, keyspace is not
        # Unbounded continuation from the same cursor keeps going.
        rows, done = c.leaf_hashes_page(100, "hp09")
        assert [r[0] for r in rows] == [f"hp{i}" for i in range(10, 20)]
        # Degenerate bound is a parse error, not silent weirdness.
        with pytest.raises(ProtocolError, match="upto"):
            c.leaf_hashes_page(10, "hp09", upto="hp04")
        # Client refuses the inexpressible empty-cursor + bound form.
        with pytest.raises(ValueError):
            c.leaf_hashes_page(10, "", upto="hp04")


def test_hashpage_follows_the_key_index_through_churn(monkeypatch):
    """HASHPAGE pages come from the engine's sorted key index: after sets,
    tombstoned and quiet deletes, re-sets, tombstone eviction and a
    truncate, paging the whole keyspace (and one bounded range) yields
    exactly the live keys and tombstones, in order, each once."""
    import random

    monkeypatch.setenv("MKV_MAX_TOMBS_PER_SHARD", "4")  # force evictions
    eng = NativeEngine("mem")
    srv = NativeServer(eng, "127.0.0.1", 0)
    srv.start()
    rng = random.Random(7)

    def expected(lo: str = "", hi: str = "\U0010ffff") -> list:
        live = {k.decode(): False for k in eng.scan()}
        tombs = {k.decode(): True for k, _ in eng.tombstones()}
        assert not live.keys() & tombs.keys()
        rows = sorted({**live, **tombs}.items())
        return [r for r in rows if lo < r[0] < hi]

    def paged(c, size: int) -> list:
        out, cursor = [], ""
        while True:
            rows, done = c.leaf_hashes_page(size, cursor)
            out += [(k, h is None) for k, h, _ in rows]
            if done:
                return out
            cursor = rows[-1][0]

    try:
        with MerkleKVClient("127.0.0.1", srv.port) as c:
            for step in range(600):
                k = f"ix{rng.randrange(200):03d}".encode()
                op = rng.random()
                if op < 0.55:
                    eng.set(k, b"v%d" % step)
                elif op < 0.85:
                    eng.delete(k)
                else:
                    eng.delete_quiet(k)
            assert eng.tomb_evictions() > 0
            want = expected()
            assert len(want) > 100
            for size in (1, 7, 512):
                assert paged(c, size) == want
            rows, done = c.leaf_hashes_page(1000, "ix050", upto="ix120")
            assert done
            assert [(k, h is None) for k, h, _ in rows] == expected(
                "ix050", "ix120")
            eng.truncate()
            assert paged(c, 16) == []
    finally:
        srv.close()
        eng.close()


# ------------------------------------------------------------ the walk


def test_bisect_converges_and_roots_match_every_engine(two_nodes):
    """Walk parity: after a bisection sync both peers, the CPU golden tree,
    the device-resident tree, and the native host tree agree bit-exactly."""
    from merklekv_tpu.merkle.cpu import MerkleTree
    from merklekv_tpu.merkle.incremental import DeviceMerkleState

    (local_eng, _), (remote_eng, remote_srv) = two_nodes
    items = {f"bk{i:04d}": f"v{i}" for i in range(800)}
    fill(remote_eng, items)
    fill(local_eng, items)
    for i in range(0, 800, 97):
        local_eng.set(f"bk{i:04d}".encode(), b"stale")
    local_eng.set(b"bk-local-only", b"x")
    remote_eng.delete(b"bk0400")
    remote_eng.set(b"bk-remote-only", b"y")

    mgr = SyncManager(local_eng, device="cpu", mode="bisect")
    report = mgr.sync_once("127.0.0.1", remote_srv.port)

    assert report.mode == "bisect"
    assert report.rounds > 0 and report.nodes_compared > 0
    assert report.bytes_sent > 0 and report.bytes_received > 0
    assert local_eng.snapshot() == remote_eng.snapshot()

    native_root = local_eng.merkle_root()
    assert native_root == remote_eng.merkle_root()
    golden = MerkleTree.from_items(
        [
            (k.decode(), v)
            for k, v in local_eng.snapshot()
        ]
    )
    assert golden.root_hash() == native_root
    device = DeviceMerkleState.from_items(local_eng.snapshot())
    assert device.root_hash() == native_root


def test_bisect_one_divergent_key_in_1m_costs_kilobytes(two_nodes):
    """THE acceptance bar: 1 divergent key in a >= 1M-key keyspace syncs
    with a few KB on the wire. Hash-first ships the whole digest list
    (~32 MB of raw digests, ~70 MB as wire hex) whenever roots differ —
    the walk replaces that with O(log n) interior nodes + one bounded leaf
    page + one value.

    Deliberately tier-1 (the acceptance bar demands the >= 1M-key scale):
    measured ~28 s on the CI-class CPU — the bulk is the 2x1M engine fills
    and the one-time local/remote tree builds, well inside the tier-1
    budget."""
    (local_eng, _), (remote_eng, remote_srv) = two_nodes
    n = 1 << 20
    for i in range(n):
        k = b"u%07d" % i
        v = b"val-%d" % (i % 9973)
        local_eng.set(k, v)
        remote_eng.set(k, v)
    local_eng.set(b"u0524288", b"DIVERGED")  # 1 stale key in the middle

    mgr = SyncManager(local_eng, device="cpu", mode="bisect")
    report = mgr.sync_once("127.0.0.1", remote_srv.port)

    assert report.mode == "bisect"
    assert report.divergent == 1
    assert report.set_keys == 1 and report.values_fetched == 1
    wire = report.bytes_sent + report.bytes_received
    # "A few hundred KB" is the acceptance ceiling; the walk actually lands
    # near ~5 KB (log2(1M) TREELEVEL rounds + one 16-leaf page + 1 value).
    # Hash-first at this size ships >= 32 MB of digests.
    assert wire < 300_000, f"walk cost {wire} bytes"
    assert wire < (n * 32) // 100, "not even 1% of the raw digest list"

    # Converged roots are bit-identical: both peers' native trees and the
    # CPU golden spec (the device tree's parity at this scale is covered by
    # the jax golden suites; see test_bisect_converges_... for the
    # in-sync-path device check).
    from merklekv_tpu.merkle.cpu import build_levels
    from merklekv_tpu.merkle.encoding import leaf_hash

    native_root = local_eng.merkle_root()
    assert native_root == remote_eng.merkle_root()
    golden_root = build_levels(
        [leaf_hash(k, v) for k, v in local_eng.snapshot()]
    )[-1][0]
    assert golden_root == native_root

    # Observability: the cycle's transfer cost landed in the metrics.
    from merklekv_tpu.utils.tracing import get_metrics

    counters = get_metrics().snapshot()["counters"]
    assert counters.get("sync.bytes_sent", 0) > 0
    assert counters.get("sync.bytes_received", 0) > 0
    assert counters.get("sync.nodes_compared", 0) > 0
    assert counters.get("sync.rounds", 0) > 0


def test_auto_mode_selects_by_keyspace_size(two_nodes):
    """auto = paged below the threshold (fewer round trips), bisect at or
    above it; "page" pins the scan even on a big keyspace."""
    (local_eng, _), (remote_eng, remote_srv) = two_nodes
    items = {f"am{i:03d}": f"v{i}" for i in range(400)}
    fill(remote_eng, items)
    fill(local_eng, items)
    local_eng.set(b"am000", b"stale")

    r = SyncManager(local_eng, device="cpu").sync_once(
        "127.0.0.1", remote_srv.port
    )
    assert r.mode == "hash-paged"  # 400 < default threshold

    local_eng.set(b"am001", b"stale")
    r = SyncManager(
        local_eng, device="cpu", bisect_threshold=100
    ).sync_once("127.0.0.1", remote_srv.port)
    assert r.mode == "bisect"

    local_eng.set(b"am002", b"stale")
    r = SyncManager(
        local_eng, device="cpu", mode="page", bisect_threshold=100
    ).sync_once("127.0.0.1", remote_srv.port)
    assert r.mode == "hash-paged"
    assert local_eng.snapshot() == remote_eng.snapshot()


def test_bisect_falls_back_without_treelevel(two_nodes, monkeypatch):
    """A peer that answers ERROR to TREELEVEL (old binary) degrades to the
    paged scan in the same cycle — no wedging, still converges."""
    (local_eng, _), (remote_eng, remote_srv) = two_nodes
    items = {f"fb{i:03d}": f"v{i}" for i in range(300)}
    fill(remote_eng, items)
    fill(local_eng, items)
    local_eng.set(b"fb000", b"stale")

    def no_treelevel(self, level, lo, hi):
        raise ProtocolError("Unknown command: TREELEVEL")

    monkeypatch.setattr(MerkleKVClient, "tree_level", no_treelevel)
    mgr = SyncManager(local_eng, device="cpu", mode="bisect")
    report = mgr.sync_once("127.0.0.1", remote_srv.port)
    assert report.mode == "hash-paged"
    assert local_eng.snapshot() == remote_eng.snapshot()


def test_bisect_empty_remote_clears_local(two_nodes):
    (local_eng, _), (_, remote_srv) = two_nodes
    fill(local_eng, {f"er{i}": "v" for i in range(50)})
    mgr = SyncManager(local_eng, device="cpu", mode="bisect")
    report = mgr.sync_once("127.0.0.1", remote_srv.port)
    # Empty peer: the walk declines (nothing to bisect) and paging mirrors
    # the emptiness.
    assert report.mode == "hash-paged"
    assert local_eng.dbsize() == 0


# ------------------------------------------------ faults + resume


FAST = RetryPolicy(
    first_delay=0.01,
    max_delay=0.05,
    jitter=0.0,
    attempts=2,
    op_timeout=0.5,
    op_deadline=30.0,
)


def test_bisect_walk_resumes_from_checkpoint_under_kill(two_nodes):
    """A stream killed mid-walk checkpoints (cursor, walk=True) into the
    SyncSession; the next cycle resumes the WALK (not the paged scan) from
    the verified frontier and the pair converges."""
    from merklekv_tpu.testing.faults import FaultInjector

    (local_eng, _), (remote_eng, remote_srv) = two_nodes
    base = {f"fw{i:04d}": f"v{i}" for i in range(600)}
    fill(remote_eng, base)
    fill(local_eng, base)
    # Spread divergence so the repair stream is long enough to kill.
    for i in range(0, 600, 3):
        local_eng.set(f"fw{i:04d}".encode(), b"stale")

    inj = FaultInjector("127.0.0.1", remote_srv.port, seed=17)
    peer = f"{inj.host}:{inj.port}"
    degraded: list[tuple[str, str]] = []
    mgr = SyncManager(
        local_eng,
        device="cpu",
        mode="bisect",
        mget_batch=8,
        hash_page=32,
        retry=FAST,
        on_peer_degraded=lambda p, r: degraded.append((p, r)),
    )
    try:
        inj.kill_after_bytes(6000, direction="s2c")
        with pytest.raises(Exception):
            mgr.sync_once(inj.host, inj.port)
        sess = mgr.session_for(peer)
        assert sess is not None, "mid-walk death must checkpoint"
        assert sess.walk, "checkpoint must remember the walk mode"
        assert degraded, "mid-walk death must degrade the peer"

        inj.revive()
        resumed = False
        for _ in range(40):
            try:
                report = mgr.sync_once(inj.host, inj.port)
                resumed = resumed or report.resumed
            except Exception:
                continue
            if local_eng.merkle_root() == remote_eng.merkle_root():
                break
        assert resumed, "at least one cycle must resume the session"
        assert local_eng.merkle_root() == remote_eng.merkle_root()
        assert local_eng.snapshot() == remote_eng.snapshot()
    finally:
        inj.close()


def test_bisect_walk_converges_under_drop_and_truncate(two_nodes):
    """Chunk drops + truncation faults on the walk path: individual cycles
    may die, but checkpoint/resume keeps progress monotonic and the pair
    converges (the satellite chaos bar for the new transfer mode)."""
    from merklekv_tpu.testing.faults import FaultInjector

    (local_eng, _), (remote_eng, remote_srv) = two_nodes
    base = {f"dt{i:04d}": f"v{i}" for i in range(500)}
    fill(remote_eng, base)
    fill(local_eng, {f"dt{i:04d}": "stale" for i in range(250)})

    inj = FaultInjector("127.0.0.1", remote_srv.port, seed=23)
    mgr = SyncManager(
        local_eng, device="cpu", mode="bisect",
        mget_batch=16, hash_page=32, retry=FAST,
    )
    try:
        inj.set_faults(direction="s2c", drop_rate=0.03, truncate_rate=0.02)
        converged = False
        for _ in range(60):
            try:
                mgr.sync_once(inj.host, inj.port)
            except Exception:
                pass
            if local_eng.merkle_root() == remote_eng.merkle_root():
                converged = True
                break
        assert converged, (
            f"no convergence (dropped={inj.chunks_dropped})"
        )
        assert local_eng.snapshot() == remote_eng.snapshot()
    finally:
        inj.close()


# ----------------------------------------- device-mirror TREELEVEL serving


def test_treelevel_device_mirror_matches_native_host_tree(two_nodes):
    """The cluster callback serves TREELEVEL from the device-resident tree
    (promotion-chain corrected); its digests are bit-identical to the
    native server's host-tree fallback for every level."""
    from types import SimpleNamespace

    from merklekv_tpu.cluster.mirror import DeviceTreeMirror
    from merklekv_tpu.cluster.node import ClusterNode
    from merklekv_tpu.config import Config

    (eng, srv), (_, _) = two_nodes
    fill(eng, {f"dm{i:03d}": f"v{i}" for i in range(100)})

    with MerkleKVClient("127.0.0.1", srv.port) as c:
        native = {}
        lvl = 0
        while True:
            rows, n = c.tree_level(lvl, 0, 10**6)
            if not rows:
                break
            native[lvl] = rows
            lvl += 1
    assert n == 100 and len(native) >= 2

    node = ClusterNode(Config(), eng, srv)
    mirror = DeviceTreeMirror(eng)
    try:
        mirror.root_hex()  # force the device state build
        node._mirror = mirror
        node._replicator = SimpleNamespace(flush=lambda: None)
        for lvl, rows in native.items():
            resp = node._on_cluster_command(f"TREELEVEL {lvl} 0 1000000")
            assert resp is not None and resp.startswith(
                f"NODES {len(rows)} 100\r\n"
            )
            body = resp.split("\r\n")[1:-1]
            got = [tuple(line.split(" ")) for line in body]
            assert got == [(str(i), h) for i, h in rows], f"level {lvl}"
    finally:
        mirror.close()


# ------------------------------------- tombstone eviction (satellite)


def test_evicted_tombstone_still_blocks_resurrection(monkeypatch):
    """The tombstone-eviction resurrection hole: fill shards past the
    (shrunken) cap so the target deletion's tombstone is EVICTED, then LWW-
    sync against a stale peer still holding the old value — the delete must
    survive via the evicted-ts high-water mark."""
    monkeypatch.setenv("MKV_MAX_TOMBS_PER_SHARD", "4")
    a = NativeEngine("mem")
    monkeypatch.delenv("MKV_MAX_TOMBS_PER_SHARD")
    b = NativeEngine("mem")
    srv_b = NativeServer(b, "127.0.0.1", 0)
    srv_b.start()
    try:
        old_ts = 1_000
        a.set_with_ts(b"victim", b"old-value", old_ts)
        b.set_with_ts(b"victim", b"old-value", old_ts)  # stale peer copy
        a.delete(b"victim")  # tombstone at "now" >> old_ts
        assert a.tombstone_ts(b"victim") is not None
        # Flood deletions: every shard blows past the 4-tombstone cap, so
        # the victim's tombstone is evicted (oldest go first).
        for i in range(400):
            a.set(b"flood%03d" % i, b"x")
            a.delete(b"flood%03d" % i)
        assert a.tomb_evictions() > 0
        assert a.tombstone_ts(b"victim") is None, "tombstone must be evicted"

        # Engine-level: a stale LWW install below the evicted mark loses.
        assert not a.set_if_newer(b"victim", b"old-value", old_ts)
        # ...but a LIVE key is exempt from the mark: an update newer than
        # its entry must apply even with ts below the HWM — rejecting it
        # would pin the stale value, buying no deletion-stability.
        a.set_with_ts(b"livekey", b"v1", 500)
        assert a.set_if_newer(b"livekey", b"v2", 600)
        assert a.get(b"livekey") == b"v2"
        # A genuinely fresh write still wins (the mark is a floor, not a
        # freeze).
        import time as _t

        now = int(_t.time() * 1e9)
        assert a.set_if_newer(b"victim", b"fresh", now)
        a.delete(b"victim")

        # Cluster-level: multi-peer LWW sync against the stale peer must
        # not resurrect the deletion.
        mgr = SyncManager(a, device="cpu")
        mgr.sync_multi([f"127.0.0.1:{srv_b.port}"])
        assert a.get(b"victim") is None, "evicted deletion was resurrected"
    finally:
        srv_b.close()
        a.close()
        b.close()


def test_config_parses_walk_settings():
    from merklekv_tpu.config import Config

    cfg = Config.from_dict(
        {"anti_entropy": {"mode": "bisect", "bisect_threshold": 123}}
    )
    assert cfg.anti_entropy.mode == "bisect"
    assert cfg.anti_entropy.bisect_threshold == 123
    assert Config.from_dict({}).anti_entropy.mode == "auto"
    with pytest.raises(ValueError, match="mode"):
        Config.from_dict({"anti_entropy": {"mode": "zigzag"}})
