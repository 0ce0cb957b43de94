"""Flight recorder & post-mortem forensics plane (ISSUE 10).

The always-on black box: the event ring + metric sampler + CRC-framed
spill (obs/flightrec.py), the native slow-command log behind the FLIGHT
verb, the subsystem hooks (degradation, peer health, sync cycles, storage
latches), the offline ``blackbox`` analyzer, and the chaos acceptance
paths — kill -9 under write load always leaves a parseable spill whose
tail names the final transitions, and the spill reader survives
truncation at every byte offset.
"""

import asyncio
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from merklekv_tpu.client import AsyncMerkleKVClient, MerkleKVClient
from merklekv_tpu.config import Config
from merklekv_tpu.native_bindings import NativeEngine, NativeServer
from merklekv_tpu.obs import flightrec
from merklekv_tpu.obs.blackbox import (
    find_anomalies,
    link_traces,
    load_docs,
    main as blackbox_main,
    merge_timeline,
)
from merklekv_tpu.obs.flightrec import (
    FlightRecorder,
    FlightSpiller,
    MetricSampler,
    Sample,
    read_spill,
    write_spill,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def server():
    eng = NativeEngine("mem")
    srv = NativeServer(eng, "127.0.0.1", 0)
    srv.start()
    yield eng, srv
    srv.close()
    eng.close()


@pytest.fixture
def node(server):
    from merklekv_tpu.cluster.node import ClusterNode

    eng, srv = server
    cfg = Config()
    cfg.observability.slow_command_us = 1  # everything is "slow"
    n = ClusterNode(cfg, eng, srv)
    flightrec.get_recorder().clear()
    n.start()
    yield eng, srv, n
    n.stop()


# ------------------------------------------------------------- ring + wire

def test_ring_capacity_order_and_drops():
    r = FlightRecorder(capacity=16)
    for i in range(40):
        r.record("tick", i=i)
    evs = r.last(0)
    assert len(evs) == 16
    assert [e.fields["i"] for e in evs] == list(range(24, 40))
    assert r.dropped() == 24
    # seq is monotonic and survives the ring's eviction
    assert [e.seq for e in evs] == list(range(25, 41))
    assert all(e.wall_ns > 0 and e.mono_ns > 0 for e in evs)


def test_record_survives_hostile_fields():
    class Boom:
        def __str__(self):
            raise RuntimeError("no repr for you")

    r = FlightRecorder()
    r.record("hostile", bad=Boom(), good=7)
    (ev,) = r.last(1)
    assert ev.fields == {"good": 7}  # bad field dropped, event kept


def test_wire_row_squeezes_all_whitespace():
    """A multi-line reason (an OSError message with an embedded newline)
    must not split the k=v row — that would desync the client's
    field-table framing (a fragment equal to 'END' ends the table early)."""
    r = FlightRecorder()
    r.record("storage_full", reason="line one\nEND\r\nline two\ttabbed")
    row = r.last(1)[0].wire_row()
    assert "\n" not in row and "\r" not in row and "\t" not in row
    assert "reason=line_one_END_line_two_tabbed" in row


def test_wire_dump_shape_newest_first():
    r = FlightRecorder()
    r.record("a", x=1)
    r.record("b", note="two words")
    dump = r.wire_dump(8)
    lines = dump.split("\r\n")
    assert lines[0] == "EVENTS 2"
    assert "kind=b" in lines[1] and "note=two_words" in lines[1]
    assert "kind=a" in lines[2] and "x=1" in lines[2]
    assert lines[3] == "END"


def test_record_stamps_active_trace_context():
    from merklekv_tpu.obs import tracewire

    r = FlightRecorder()
    ctx = tracewire.new_context()
    with tracewire.trace_scope(ctx):
        r.record("traced_thing")
    (ev,) = r.last(1)
    assert ev.fields.get("trace") == f"{ctx.trace_id:016x}"


# ------------------------------------------------------------------ sampler

def test_sampler_snapshots_and_derives_watch_events():
    stats = {"busy_rejected_connections": 0, "total_commands": 5}

    def stats_fn():
        return "".join(f"{k}:{v}\r\n" for k, v in stats.items())

    rec = FlightRecorder()
    s = MetricSampler(interval_s=0.05, stats_fn=stats_fn, recorder=rec)
    first = s.sample_once()
    assert first.values["native.total_commands"] == 5
    assert not [e for e in rec.last(0) if e.kind == "admission_reject"]
    stats["busy_rejected_connections"] = 7
    s.sample_once()
    evs = [e for e in rec.last(0) if e.kind == "admission_reject"]
    assert len(evs) == 1 and evs[0].fields["count"] == 7
    # no further delta -> no further event
    s.sample_once()
    assert len([e for e in rec.last(0) if e.kind == "admission_reject"]) == 1
    assert len(s.samples(0)) == 3


def test_sampler_window_is_bounded():
    s = MetricSampler(interval_s=1.0, window_s=5.0)
    for _ in range(20):
        s.sample_once()
    assert len(s.samples(0)) == 5


# -------------------------------------------------------------------- spill

def _make_doc():
    r = FlightRecorder()
    r.record("node_start", port=1234)
    r.record("degradation", prev="live", new="shedding", reason="memory")
    r.record("slow_command", verb="GET", dur_us=15000, conn="1.2.3.4:5")
    samples = [
        Sample(wall_ns=time.time_ns(),
               values={"native.total_commands": i, "keyspace.keys": 10 + i})
        for i in range(3)
    ]
    return r.last(0), samples


def test_spill_roundtrip(tmp_path):
    events, samples = _make_doc()
    path = str(tmp_path / "flight.bin")
    write_spill(path, events, samples, node="n1:1234", note="unit")
    doc = read_spill(path)
    assert not doc.truncated and doc.error == ""
    assert doc.meta["node"] == "n1:1234" and doc.meta["note"] == "unit"
    assert [e.kind for e in doc.events] == [e.kind for e in events]
    assert doc.events[1].fields["new"] == "shedding"
    assert len(doc.samples) == 3
    assert doc.samples[2].values["keyspace.keys"] == 12


def test_spill_rewrite_is_atomic(tmp_path):
    """A torn tmp write (the kill -9 shape) never disturbs the previous
    complete spill under the final name."""
    events, samples = _make_doc()
    path = str(tmp_path / "flight.bin")
    write_spill(path, events, samples, node="gen1")
    with open(path + ".tmp", "wb") as f:
        f.write(b"MKVFLT1\n\x99\x99")  # a cut-off rewrite attempt
    doc = read_spill(path)
    assert doc.meta["node"] == "gen1" and not doc.truncated


def test_spill_reader_survives_truncation_at_every_offset(tmp_path):
    """Fuzz requirement from the ISSUE: truncate the spill at EVERY byte
    offset; the reader must never raise past the magic check and must
    return an intact prefix."""
    events, samples = _make_doc()
    path = str(tmp_path / "flight.bin")
    write_spill(path, events, samples, node="n1:1")
    with open(path, "rb") as f:
        data = f.read()
    full = read_spill(path)
    # Frame boundaries: a cut exactly there is indistinguishable from a
    # shorter complete spill (no truncated flag expected); everywhere else
    # the reader must flag truncation. Either way it must never raise and
    # must return an intact prefix.
    boundaries = {len(flightrec.SPILL_MAGIC)}
    off = len(flightrec.SPILL_MAGIC)
    while off < len(data):
        (length,) = flightrec._FRAME_HDR.unpack_from(data, off)[:1]
        off += flightrec._FRAME_HDR.size + length
        boundaries.add(off)
    tpath = str(tmp_path / "trunc.bin")
    for cut in range(len(data)):
        with open(tpath, "wb") as f:
            f.write(data[:cut])
        if cut < len(flightrec.SPILL_MAGIC):
            doc = read_spill(tpath)
            assert doc.truncated and not doc.events
            continue
        doc = read_spill(tpath)
        if cut not in boundaries:
            assert doc.truncated, f"cut at {cut} not flagged"
        # the parsed prefix is always a prefix of the full doc
        assert [e.seq for e in doc.events] == [
            e.seq for e in full.events[: len(doc.events)]
        ]
        assert len(doc.samples) <= len(full.samples)


def test_spill_reader_survives_byte_flips(tmp_path):
    events, samples = _make_doc()
    path = str(tmp_path / "flight.bin")
    write_spill(path, events, samples, node="n1:1")
    with open(path, "rb") as f:
        data = f.read()
    rng = random.Random(42)
    fpath = str(tmp_path / "flip.bin")
    for _ in range(48):
        i = rng.randrange(len(flightrec.SPILL_MAGIC), len(data))
        flipped = bytearray(data)
        flipped[i] ^= 0xFF
        with open(fpath, "wb") as f:
            f.write(bytes(flipped))
        doc = read_spill(fpath)  # must not raise
        # CRC framing: a flipped payload/header byte stops parsing, it
        # never yields a silently-corrupt frame; frames before the flip
        # still parse.
        assert doc.truncated or len(doc.events) == len(events)


def test_spill_rejects_foreign_file(tmp_path):
    p = tmp_path / "notaspill.bin"
    p.write_bytes(b"definitely not a spill file\n")
    with pytest.raises(ValueError):
        read_spill(str(p))


def test_spiller_start_raises_on_unwritable_dir(tmp_path):
    """The first (inline) spill is strict: a misconfigured flight dir
    fails start() loudly so the node can disable the spiller and warn,
    instead of a background thread retrying a doomed write forever."""
    # A regular FILE where a directory is needed: makedirs fails with
    # ENOTDIR for any uid (permission bits would not stop a root test
    # runner).
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    sp = FlightSpiller(str(blocker / "flight"), recorder=FlightRecorder(),
                       interval_s=30.0)
    with pytest.raises(OSError):
        sp.start()
    assert sp._thread is None  # the periodic loop never started


def test_spiller_writes_initial_and_final(tmp_path):
    rec = FlightRecorder()
    rec.record("node_start", port=1)
    sp = FlightSpiller(str(tmp_path), recorder=rec, interval_s=30.0,
                       node="n1:1")
    sp.start()  # initial spill is inline, no interval wait needed
    doc = read_spill(sp.path)
    assert [e.kind for e in doc.events] == ["node_start"]
    rec.record("node_stop")
    sp.stop(final=True)
    doc = read_spill(sp.path)
    assert [e.kind for e in doc.events] == ["node_start", "node_stop"]


# ------------------------------------------------------------ config plane

def test_config_flight_validation():
    base = {"observability": {}}
    assert Config.from_dict(base).observability.flight_enabled
    cfg = Config.from_dict(
        {"observability": {"flight_sample_s": 0.5, "flight_spill_s": 2,
                           "flight_events": 64, "slow_command_us": 500,
                           "flight_dir": "/tmp/f"}}
    )
    assert cfg.observability.flight_sample_s == 0.5
    assert cfg.observability.slow_command_us == 500
    for bad in (
        {"flight_sample_s": 0},
        {"flight_spill_s": -1},
        {"flight_events": 4},
        {"slow_command_us": -2},
    ):
        with pytest.raises(ValueError):
            Config.from_dict({"observability": bad})


def test_bench_gate_flight_overhead_is_down_good():
    from tools.bench_gate import lower_is_better

    assert lower_is_better("flight_overhead_pct", "% (median)")


# --------------------------------------------- native slow log + FLIGHT verb

def test_native_flight_fallback_serves_slow_log(server):
    eng, srv = server
    srv.set_slow_threshold(1)
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        c.set("k", "v")
        c.get("k")
        rows = c.flight(16)
        assert rows, "bare node must serve its slow-command log"
        assert all(r["kind"] == "slow_command" for r in rows)
        verbs = {r["verb"] for r in rows}
        assert {"SET", "GET"} <= verbs
        assert all(int(r["dur_us"]) >= 1 for r in rows)
        assert all(int(r["wall_ns"]) > 0 for r in rows)
        # newest first
        seqs = [int(r["seq"]) for r in rows]
        assert seqs == sorted(seqs, reverse=True)
        assert int(c.stats()["slow_commands"]) >= len(rows)


def test_slow_threshold_off_means_no_log(server):
    eng, srv = server
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        c.set("k", "v")
        assert c.flight(8) == []
        assert int(c.stats()["slow_commands"]) == 0


def test_flight_parse_errors(server):
    eng, srv = server
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        assert c._request("FLIGHT 0").startswith("ERROR")
        assert c._request("FLIGHT x").startswith("ERROR")
        assert c._request("FLIGHT 1 2").startswith("ERROR")


def test_flight_stays_open_while_loading_and_degraded(server):
    """Forensics must answer exactly when the node is sick: the FLIGHT
    verb serves through the bootstrap LOADING gate and at every
    degradation rung."""
    eng, srv = server
    srv.set_slow_threshold(1)
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        c.set("k", "v")
        srv.set_serving(False)
        try:
            assert c.flight(4)  # no ERROR LOADING
        finally:
            srv.set_serving(True)
        srv.set_degradation(2, 1)  # read_only (memory)
        try:
            assert c.flight(4)
        finally:
            srv.set_degradation(0, 0)


def test_node_flight_ring_merges_slowcmd_relay(node):
    """With a control plane attached, FLIGHT serves the python ring — and
    native slow commands reach it through the SLOWCMD notification."""
    eng, srv, n = node
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        c.set("k", "v")
        deadline = time.time() + 5
        rows = []
        while time.time() < deadline:
            rows = c.flight(32)
            if any(r["kind"] == "slow_command" for r in rows):
                break
            time.sleep(0.02)
        kinds = {r["kind"] for r in rows}
        assert "slow_command" in kinds, rows
        assert "node_start" in kinds, rows
        slow = [r for r in rows if r["kind"] == "slow_command"][0]
        assert slow["verb"] in ("SET", "GET", "PING")
        assert int(slow["dur_us"]) >= 1


def test_async_client_flight_parity(node):
    eng, srv, n = node

    async def go():
        async with AsyncMerkleKVClient("127.0.0.1", srv.port) as c:
            await c.set("ak", "av")
            await asyncio.sleep(0.05)
            return await c.flight(32)

    rows = asyncio.run(go())
    assert any(r["kind"] == "node_start" for r in rows)


def test_slow_threshold_disarmed_on_node_stop(server):
    """A stopped node must not leave its slow-command threshold armed on
    an embedded server a successor (or a flight-disabled node) reuses."""
    from merklekv_tpu.cluster.node import ClusterNode

    eng, srv = server
    cfg = Config()
    cfg.observability.slow_command_us = 1
    n = ClusterNode(cfg, eng, srv)
    n.start()
    with MerkleKVClient("127.0.0.1", srv.port) as c:
        c.set("k", "v")
        assert int(c.stats()["slow_commands"]) > 0
        n.stop()
        before = int(c.stats()["slow_commands"])
        for i in range(5):
            c.set(f"post{i}", "v")
        assert int(c.stats()["slow_commands"]) == before


# ------------------------------------------------------------ subsystem hooks

def test_degradation_transition_records_event():
    from merklekv_tpu.cluster.overload import DegradationLadder, OverloadMonitor
    from merklekv_tpu.config import ServerConfig

    class SrvStub:
        def set_degradation(self, level, reason):
            pass

    eng = NativeEngine("mem")
    try:
        eng.set(b"k", b"v" * 128)
        rec = flightrec.get_recorder()
        rec.clear()
        mon = OverloadMonitor(
            DegradationLadder(), eng, SrvStub(),
            ServerConfig(memory_soft_bytes=1), interval=9999,
        )
        mon.poll_once()
        evs = [e for e in rec.last(0) if e.kind == "degradation"]
        assert evs and evs[-1].fields["new"] == "shedding"
        assert evs[-1].fields["prev"] == "live"
    finally:
        eng.close()


def test_storage_full_latch_and_recovery_record_events(tmp_path):
    from merklekv_tpu.config import StorageConfig
    from merklekv_tpu.storage.store import DurableStore
    from merklekv_tpu.testing.faults import WalErrnoInjector

    rec = flightrec.get_recorder()
    rec.clear()
    eng = NativeEngine("mem")
    st = DurableStore(eng, StorageConfig(), str(tmp_path))
    st.recover()
    try:
        inj = WalErrnoInjector(fail_write_at=1).install()
        try:
            eng.set_with_ts(b"k", b"v", 1)
            st.record_set(b"k", b"v", 1)
            assert st.storage_full
            inj.heal()
            st._check_disk()
            assert not st.storage_full
        finally:
            inj.uninstall()
        kinds = [e.kind for e in rec.last(0)]
        assert "storage_full" in kinds and "storage_recovered" in kinds
        assert kinds.index("storage_full") < kinds.index("storage_recovered")
    finally:
        st.stop()
        eng.close()


def test_full_backoff_resets_after_completed_snapshot(tmp_path):
    """Fast regression for the (formerly flaky) disk-full soak: a
    COMPLETED re-anchor snapshot must fully reset the probe-flap detector,
    so the NEXT genuine full episode recovers on its first post-heal
    probe instead of being deferred as a flap."""
    from merklekv_tpu.config import StorageConfig
    from merklekv_tpu.storage.store import DurableStore
    from merklekv_tpu.testing.faults import WalErrnoInjector

    eng = NativeEngine("mem")
    st = DurableStore(eng, StorageConfig(), str(tmp_path))
    st.recover()
    try:
        for cycle in (1, 2):
            inj = WalErrnoInjector(fail_write_at=1).install()
            try:
                eng.set_with_ts(b"k%d" % cycle, b"v", cycle)
                st.record_set(b"k%d" % cycle, b"v", cycle)
                assert st.storage_full
                inj.heal()
                st._check_disk()
                assert not st.storage_full, (
                    f"cycle {cycle}: recovery deferred by stale flap backoff"
                )
                st.snapshot_now()
                st._snapshot_requested = False
            finally:
                inj.uninstall()
    finally:
        st.stop()
        eng.close()


def test_sync_cycle_outcome_records_event():
    from merklekv_tpu.obs.trace import CycleTrace, PeerTrace, get_trace_buffer

    rec = flightrec.get_recorder()
    rec.clear()
    get_trace_buffer().append(
        CycleTrace(
            cycle_id=99, kind="pairwise", seconds=0.5,
            peers=[
                PeerTrace(peer="a:1", outcome="ok", repairs=2),
                PeerTrace(peer="b:2", outcome="error", error="boom"),
            ],
        )
    )
    evs = [e for e in rec.last(0) if e.kind == "sync_cycle"]
    assert evs and evs[-1].fields["outcome"] == "error"
    assert evs[-1].fields["repairs"] == 2
    assert evs[-1].fields["cycle"] == 99


def test_peer_health_flip_records_event():
    from merklekv_tpu.cluster.health import PeerHealthMonitor

    rec = flightrec.get_recorder()
    rec.clear()
    mon = PeerHealthMonitor(["127.0.0.1:1"], down_after=1, timeout=0.2)
    mon.probe_all()  # nothing listens on port 1: flips unknown -> down
    evs = [e for e in rec.last(0) if e.kind == "peer_health"]
    assert evs and evs[-1].fields["new"] == "down"
    mon.mark_degraded("x:9", "stream died")
    evs = [e for e in rec.last(0) if e.kind == "peer_health"]
    assert evs[-1].fields["new"] == "degraded"
    assert evs[-1].fields["prev"] == "unknown"  # provenance of the flip


def test_bootstrap_state_records_events():
    from merklekv_tpu.cluster.bootstrap import BootstrapSession

    rec = flightrec.get_recorder()
    rec.clear()
    sess = BootstrapSession.__new__(BootstrapSession)
    sess._state = "idle"
    sess._state_mu = threading.Lock()
    sess._enter("discover")
    sess._enter("fetch")
    states = [
        e.fields["state"] for e in rec.last(0) if e.kind == "bootstrap"
    ]
    assert states == ["discover", "fetch"]


# ---------------------------------------------------------------- blackbox

def _spill_pair(tmp_path):
    d1, d2 = tmp_path / "a", tmp_path / "b"
    r1 = FlightRecorder()
    r1.record("node_start", port=1)
    r1.record("degradation", prev="live", new="read_only", reason="disk",
              trace="cafe0000cafe0000")
    write_spill(str(d1 / "flight.bin"), r1.last(0), [], node="A:1")
    time.sleep(0.002)
    r2 = FlightRecorder()
    r2.record("node_start", port=2)
    r2.record("sync_cycle", cycle=3, outcome="error",
              trace="cafe0000cafe0000")
    write_spill(
        str(d2 / "flight.bin"),
        r2.last(0),
        [Sample(wall_ns=time.time_ns(),
                values={"replication.lag_events.A": 250})],
        node="B:2",
    )
    return str(d1), str(d2)


def test_blackbox_merges_ordered_timeline_with_trace_links(tmp_path):
    d1, d2 = _spill_pair(tmp_path)
    report = load_docs([d1, d2])
    assert not report.errors
    assert not any(doc.truncated for doc in report.docs)
    walls = [e.event.wall_ns for e in report.timeline]
    assert walls == sorted(walls)
    nodes = {e.node for e in report.timeline}
    assert nodes == {"A:1", "B:2"}
    assert report.trace_links == {"cafe0000cafe0000": ["A:1", "B:2"]}
    kinds = {a.kind for a in report.anomalies}
    assert {"degradation", "sync_failure", "lag_spike"} <= kinds


def test_blackbox_flags_device_shortfall_as_environment(tmp_path):
    """A multichip probe spill whose device-enumerate phase shows fewer
    devices than requested (a device-complement shortfall) surfaces as an
    ENVIRONMENT anomaly — triage reads driver weather, not a regression —
    while a full-complement enumerate stays silent."""
    d = tmp_path / "probe"
    r = FlightRecorder()
    r.record("multichip_phase", phase="device-count", want=8, have=1)
    write_spill(str(d / "flight.bin"), r.last(0), [], node="probe")
    report = load_docs([str(d)])
    envs = [a for a in report.anomalies if a.kind == "environment"]
    assert len(envs) == 1 and "have 1, want 8" in envs[0].detail

    d2 = tmp_path / "probe-ok"
    r2 = FlightRecorder()
    r2.record("multichip_phase", phase="device-count", want=8, have=8)
    write_spill(str(d2 / "flight.bin"), r2.last(0), [], node="probe")
    assert not [
        a for a in load_docs([str(d2)]).anomalies if a.kind == "environment"
    ]


def test_blackbox_cli_json_and_rc(tmp_path, capsys):
    d1, d2 = _spill_pair(tmp_path)
    rc = blackbox_main([d1, d2, "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out)
    assert len(doc["spills"]) == 2
    assert doc["trace_links"]
    assert all(s["error"] == "" for s in doc["spills"])
    rc = blackbox_main([d1, d2])
    text = capsys.readouterr().out
    assert rc == 0
    assert "merged timeline" in text and "anomalies" in text


def test_blackbox_unreadable_input_fails_loudly(tmp_path):
    bad = tmp_path / "garbage.bin"
    bad.write_bytes(b"not a spill")
    rc = blackbox_main([str(bad)])
    assert rc == 1


def test_blackbox_fatal_marker_lands_on_timeline(tmp_path):
    d1, d2 = _spill_pair(tmp_path)
    with open(os.path.join(d1, "fatal.txt"), "w") as f:
        f.write(f"fatal signal 11 pid 77 wall_ns {time.time_ns()}\n")
    report = load_docs([d1, d2])
    fatals = [e for e in report.timeline if e.event.kind == "fatal_signal"]
    assert fatals
    # Attributed to the node whose spill shares the marker's directory —
    # NOT the directory basename (which is the same for every node in the
    # standard <data>/node-<port>/flight layout).
    assert fatals[0].node == "A:1"
    assert any(a.kind == "fatal_signal" and a.node == "A:1"
               for a in report.anomalies)


def test_merge_preserves_per_node_seq_order_under_clock_step():
    """An NTP backwards step mid-run must not reorder one node's own
    events on the merged timeline: the k-way merge interleaves nodes by
    wall clock but each node's stream stays in sequence order."""
    t = time.time_ns()
    a = flightrec.SpillDoc(
        path="a", meta={"node": "A", "pid": 11},
        events=[
            flightrec.FlightEvent(seq=1, wall_ns=t + int(5e9), mono_ns=1,
                                  kind="storage_full", fields={}),
            # clock stepped BACK 5 s between the two events
            flightrec.FlightEvent(seq=2, wall_ns=t, mono_ns=2,
                                  kind="storage_recovered", fields={}),
        ],
    )
    b = flightrec.SpillDoc(
        path="b", meta={"node": "B", "pid": 22},
        events=[
            flightrec.FlightEvent(seq=1, wall_ns=t + int(2e9), mono_ns=1,
                                  kind="node_start", fields={}),
        ],
    )
    merged = merge_timeline([a, b])
    a_kinds = [e.event.kind for e in merged if e.node == "A"]
    assert a_kinds == ["storage_full", "storage_recovered"]
    assert len(merged) == 3


def test_merge_dedupes_shared_process_ring():
    """Two co-located nodes sharing one process spill the SAME ring to
    two dirs; the analyzer must report each event once, not double-count
    every anomaly."""
    t = time.time_ns()
    evs = [
        flightrec.FlightEvent(seq=i, wall_ns=t + i, mono_ns=i,
                              kind="degradation",
                              fields={"prev": "live", "new": "shedding"})
        for i in range(1, 4)
    ]
    a = flightrec.SpillDoc(path="a", meta={"node": "A", "pid": 77},
                           events=list(evs))
    b = flightrec.SpillDoc(path="b", meta={"node": "B", "pid": 77},
                           events=list(evs))
    merged = merge_timeline([a, b])
    assert len(merged) == 3
    assert {e.node for e in merged} == {"A"}  # first doc's attribution
    # distinct pids (real distinct processes) never dedupe
    b2 = flightrec.SpillDoc(path="b", meta={"node": "B", "pid": 78},
                            events=list(evs))
    assert len(merge_timeline([a, b2])) == 6


def test_slow_burst_anomaly_window():
    r = FlightRecorder()
    for _ in range(4):
        r.record("slow_command", verb="GET", dur_us=20000, conn="x")
    doc = flightrec.SpillDoc(path="mem", meta={"node": "N"},
                             events=r.last(0))
    anomalies = find_anomalies([doc], merge_timeline([doc]))
    bursts = [a for a in anomalies if a.kind == "slow_burst"]
    assert len(bursts) == 1  # one flag per window, not one per event


# --------------------------------------------------------------------- top

def test_top_events_pane_renders():
    from merklekv_tpu.obs.top import NodeSample, render_events_pane

    s = NodeSample(node="n1:1", ok=True)
    s.events = [
        {"seq": "3", "wall_ns": str(time.time_ns()),
         "kind": "degradation", "prev": "live", "new": "shedding"},
        {"seq": "2", "wall_ns": str(time.time_ns() - int(5e9)),
         "kind": "slow_command", "verb": "GET", "dur_us": "15000"},
    ]
    pane = render_events_pane({"n1:1": s})
    assert "flight events" in pane
    assert "degradation" in pane and "new=shedding" in pane
    assert "slow_command" in pane and "verb=GET" in pane


# ------------------------------------------------------- crash marker (native)

def test_native_crash_marker_stamps_fatal_signal(tmp_path):
    """A SIGSEGV in a real process appends the async-signal-safe marker
    line before dying; blackbox reads it as a fatal_signal event."""
    marker = str(tmp_path / "fatal.txt")
    code = (
        "import ctypes, os\n"
        "from merklekv_tpu.native_bindings import install_crash_marker\n"
        f"install_crash_marker({marker!r})\n"
        "os.kill(os.getpid(), 11)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(os.environ, PYTHONPATH=REPO),
        capture_output=True,
        timeout=60,
    )
    assert proc.returncode != 0  # died by signal
    with open(marker) as f:
        line = f.read()
    assert line.startswith("fatal signal 11 pid ")
    assert "wall_ns" in line
    from merklekv_tpu.obs.blackbox import _marker_events

    evs = _marker_events(marker)
    assert evs and evs[0].kind == "fatal_signal"
    assert evs[0].fields["signal"] == 11


# ------------------------------------------------- kill -9 chaos (integration)

def _flight_toml(path, port, data_dir):
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

[observability]
flight_spill_s = 0.2
flight_sample_s = 0.1
slow_command_us = 1
"""
    )
    return str(path)


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


@pytest.mark.integration
def test_kill9_midburst_leaves_parseable_spill_and_blackbox_merges(tmp_path):
    """The acceptance core: SIGKILL two durable nodes mid-write-burst; each
    surviving spill parses with zero errors, its tail names the final
    state transitions (and proves the death was NOT clean — no node_stop),
    and blackbox merges both into one ordered timeline, rc 0."""
    ports = _free_ports(2)
    procs = []
    try:
        for i, port in enumerate(ports):
            toml = _flight_toml(
                tmp_path / f"n{i}.toml", port, str(tmp_path / f"data{i}")
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, "-m", "merklekv_tpu", "--config", toml],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                    env=dict(os.environ, PYTHONPATH=REPO,
                             JAX_PLATFORMS="cpu"),
                )
            )
        for proc, port in zip(procs, ports):
            line = proc.stdout.readline()
            assert "listening on" in line, line
        clients = [
            MerkleKVClient("127.0.0.1", p).connect() for p in ports
        ]
        stop = threading.Event()

        def burst(c, tag):
            i = 0
            try:
                while not stop.is_set():
                    c.set(f"{tag}:{i:06d}", "v" * 32)
                    i += 1
            except Exception:
                pass  # the kill severs the connection — expected

        threads = [
            threading.Thread(target=burst, args=(c, t), daemon=True)
            for c, t in zip(clients, ("a", "b"))
        ]
        for t in threads:
            t.start()
        time.sleep(1.0)  # several spill intervals land mid-burst
        for proc in procs:
            os.kill(proc.pid, signal.SIGKILL)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        for c in clients:
            c.close()
        for proc in procs:
            proc.wait(timeout=10)

        flight_dirs = [
            os.path.join(str(tmp_path / f"data{i}"), f"node-{p}", "flight")
            for i, p in enumerate(ports)
        ]
        docs = []
        for d in flight_dirs:
            doc = read_spill(os.path.join(d, "flight.bin"))
            # Atomic rewrite: the surviving spill is COMPLETE, zero parse
            # errors, even though the process died mid-burst.
            assert not doc.truncated and doc.error == ""
            kinds = [e.kind for e in doc.events]
            # node_start is present unless the 1 us threshold flooded the
            # ring past capacity — in which case the rolled sequence
            # numbers prove the recorder kept running to the end.
            assert "node_start" in kinds or doc.events[0].seq > 1
            # the tail names the final transitions: the burst's slow
            # commands (1 us threshold) ran to the very end...
            assert doc.events[-1].kind in (
                "slow_command", "admission_reject", "events_dropped",
                "writes_shed",
            ), kinds[-5:]
            assert "slow_command" in kinds
            # ...and there is NO clean-shutdown marker: the spill alone
            # distinguishes kill -9 from a stop().
            assert "node_stop" not in kinds
            assert len(doc.samples) >= 2
            docs.append(doc)

        rc = blackbox_main([*flight_dirs, "--json"])
        assert rc == 0
        report = load_docs(flight_dirs)
        assert not report.errors
        assert {d.node for d in report.docs} == {
            f"127.0.0.1:{p}" for p in ports
        }
        walls = [e.event.wall_ns for e in report.timeline]
        assert walls == sorted(walls)
        assert len(report.timeline) >= len(docs[0].events)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
