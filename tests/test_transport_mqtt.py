"""MQTT 3.1.1 transport (VERDICT r4 item 6).

Frame-level tests against MqttBroker (real MQTT wire frames on real
sockets — CONNACK/SUBACK/PUBLISH fan-out/PINGRESP), plus end-to-end
replication between two ClusterNodes whose fabric is `transport = "mqtt"`.
"""

import socket
import struct
import time
import uuid

import pytest

from merklekv_tpu.cluster.transport_mqtt import (
    MqttTransport,
    MqttBroker,
    _topic_matches,
)


@pytest.fixture
def broker():
    b = MqttBroker()
    yield b
    b.close()


def wait_for(fn, timeout=5.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def test_topic_filter_matching():
    assert _topic_matches("a/events/#", "a/events")  # parent level
    assert _topic_matches("a/events/#", "a/events/x/y")
    assert not _topic_matches("a/events/#", "a/other")
    assert _topic_matches("a/+/c", "a/b/c")
    assert not _topic_matches("a/+/c", "a/b/d")
    assert not _topic_matches("a/+", "a/b/c")
    assert _topic_matches("#", "anything/at/all")


def test_connect_publish_subscribe_round_trip(broker):
    got = []
    t1 = MqttTransport(broker.host, broker.port, client_id="c1")
    t2 = MqttTransport(broker.host, broker.port, client_id="c2")
    try:
        t2.subscribe("t/events", lambda topic, p: got.append((topic, p)))
        time.sleep(0.05)  # let SUBACK land before publishing
        t1.publish("t/events", b"payload-1")
        assert wait_for(lambda: got == [("t/events", b"payload-1")])
        assert broker.connects == 2
        assert broker.publishes >= 1
    finally:
        t1.close()
        t2.close()


def test_publisher_receives_own_messages_like_mqtt(broker):
    """MQTT fan-out includes the publisher when it subscribes — the
    replicator's src-based loop prevention depends on seeing (and
    skipping) its own events, same as with a real broker."""
    got = []
    t = MqttTransport(broker.host, broker.port, client_id="self")
    try:
        t.subscribe("s/events", lambda topic, p: got.append(p))
        time.sleep(0.05)
        t.publish("s/events", b"echo")
        assert wait_for(lambda: got == [b"echo"])
    finally:
        t.close()


def test_frames_are_real_mqtt(broker):
    """Hand-rolled socket speaking raw MQTT 3.1.1 frames interoperates
    with the broker — proving the wire format, not just the Python API."""
    sock = socket.create_connection((broker.host, broker.port), timeout=5)
    try:
        # CONNECT: protocol name "MQTT", level 4, clean session.
        cid = b"rawcli"
        var = struct.pack(">H", 4) + b"MQTT" + bytes([4, 0x02]) + struct.pack(">H", 30)
        payload = struct.pack(">H", len(cid)) + cid
        body = var + payload
        sock.sendall(bytes([0x10, len(body)]) + body)
        connack = sock.recv(4)
        assert connack == bytes([0x20, 2, 0, 0])

        # SUBSCRIBE to raw/#
        filt = b"raw/#"
        body = struct.pack(">H", 7) + struct.pack(">H", len(filt)) + filt + b"\x00"
        sock.sendall(bytes([0x82, len(body)]) + body)
        suback = sock.recv(5)
        assert suback == bytes([0x90, 3, 0, 7, 0])

        # PUBLISH from a transport client; the raw socket must receive a
        # spec-shaped PUBLISH frame.
        t = MqttTransport(broker.host, broker.port, client_id="pub")
        try:
            t.publish("raw/events", b"xyz")
            sock.settimeout(5)
            frame = sock.recv(256)
            assert frame[0] == 0x30  # PUBLISH, QoS-0
            rem = frame[1]
            (tlen,) = struct.unpack(">H", frame[2:4])
            assert frame[4 : 4 + tlen] == b"raw/events"
            assert frame[4 + tlen :] == b"xyz"
            assert rem == 2 + tlen + 3
        finally:
            t.close()
    finally:
        sock.close()


def test_ping_keepalive(broker):
    t = MqttTransport(broker.host, broker.port, client_id="ping", keepalive=2)
    try:
        # The ping loop fires at keepalive/2 = 1s; surviving 2.5s proves
        # PINGREQ/PINGRESP round-trips don't wedge the read loop.
        got = []
        t.subscribe("ka/events", lambda topic, p: got.append(p))
        time.sleep(2.5)
        t.publish("ka/events", b"alive")
        assert wait_for(lambda: got == [b"alive"])
    finally:
        t.close()


def test_auth_fields_accepted(broker):
    t = MqttTransport(
        broker.host, broker.port, client_id="auth",
        username="u", password="p",
    )
    t.close()
    assert broker.connects >= 1


@pytest.mark.integration
def test_replication_over_mqtt_fabric(broker):
    """Two ClusterNodes whose [replication] transport = "mqtt" converge
    through the (stub, frame-accurate) MQTT broker."""
    from merklekv_tpu.client import MerkleKVClient
    from merklekv_tpu.cluster.node import ClusterNode
    from merklekv_tpu.config import Config
    from merklekv_tpu.native_bindings import NativeEngine, NativeServer

    topic = f"mq-{uuid.uuid4().hex[:8]}"

    def make_node(node_id):
        engine = NativeEngine("mem")
        server = NativeServer(engine, "127.0.0.1", 0)
        server.start()
        cfg = Config()
        cfg.replication.enabled = True
        cfg.replication.transport = "mqtt"
        cfg.replication.mqtt_broker = broker.host
        cfg.replication.mqtt_port = broker.port
        cfg.replication.topic_prefix = topic
        cfg.replication.client_id = node_id
        node = ClusterNode(cfg, engine, server)
        node.start()
        client = MerkleKVClient("127.0.0.1", server.port, timeout=15).connect()
        return engine, server, node, client

    e1, s1, n1, c1 = make_node("mq-1")
    e2, s2, n2, c2 = make_node("mq-2")
    try:
        c1.set("mqtt-key", "mqtt-value")
        assert wait_for(lambda: c2.get("mqtt-key") == "mqtt-value")
        c2.set("reverse", "path")
        assert wait_for(lambda: c1.get("reverse") == "path")
        c1.delete("mqtt-key")
        assert wait_for(lambda: c2.get("mqtt-key") is None)
        assert wait_for(lambda: c1.hash() == c2.hash())
    finally:
        for cl, nd, sv, en in ((c1, n1, s1, e1), (c2, n2, s2, e2)):
            cl.close()
            nd.stop()
            sv.close()
            en.close()


def test_unknown_transport_kind_rejected():
    from merklekv_tpu.cluster.transport import make_transport

    with pytest.raises(ValueError, match="unknown replication transport"):
        make_transport("somehost", 1883, kind="MQTT")  # typo'd case


def _raw_connect(broker) -> socket.socket:
    """Minimal third-party-style MQTT client: CONNECT and eat the CONNACK."""
    from merklekv_tpu.cluster.transport_mqtt import _encode_varlen, _utf8

    s = socket.create_connection((broker.host, broker.port), timeout=5)
    var = _utf8("MQTT") + bytes([4, 0x02]) + struct.pack(">H", 30)
    body = var + _utf8(f"raw-{uuid.uuid4().hex[:8]}")
    s.sendall(bytes([0x10]) + _encode_varlen(len(body)) + body)
    ack = s.recv(4)
    assert ack == bytes([0x20, 2, 0, 0]), ack
    return s


def test_qos1_publish_from_third_party_client(broker):
    """A QoS-1 publisher (mosquitto_pub -q 1 style) gets a PUBACK, and
    subscribers receive a CLEAN QoS-0 body — no stray packet-id bytes."""
    from merklekv_tpu.cluster.transport_mqtt import _encode_varlen, _utf8

    got = []
    sub = MqttTransport(broker.host, broker.port, client_id="q1sub")
    try:
        sub.subscribe("q1/events", lambda t, p: got.append((t, p)))
        time.sleep(0.05)
        raw = _raw_connect(broker)
        try:
            body = _utf8("q1/events/k") + struct.pack(">H", 77) + b"payload-q1"
            raw.sendall(bytes([0x32]) + _encode_varlen(len(body)) + body)
            puback = raw.recv(4)
            assert puback == bytes([0x40, 2, 0, 77]), puback
            assert wait_for(lambda: got == [("q1/events/k", b"payload-q1")]), got
        finally:
            raw.close()
    finally:
        sub.close()


def test_malformed_frame_drops_sender_only(broker):
    """An empty-body PUBLISH (malformed: no topic length) must cost the
    sender its connection and nothing else — the broker keeps serving."""
    bad = _raw_connect(broker)
    bad.sendall(bytes([0x30, 0x00]))  # PUBLISH, remaining length 0
    # Broker closes the offender (recv sees EOF within the timeout).
    bad.settimeout(5)
    assert bad.recv(16) == b""
    bad.close()

    got = []
    t1 = MqttTransport(broker.host, broker.port, client_id="after-bad-1")
    t2 = MqttTransport(broker.host, broker.port, client_id="after-bad-2")
    try:
        t2.subscribe("ok/events", lambda t, p: got.append(p))
        time.sleep(0.05)
        t1.publish("ok/events", b"still-alive")
        assert wait_for(lambda: got == [b"still-alive"])
    finally:
        t1.close()
        t2.close()


@pytest.mark.integration
def test_mqtt_broker_cli_cluster_replicates(tmp_path):
    """All-MQTT cluster, fully self-contained: the CLI broker in --protocol
    mqtt mode plus two server processes configured with transport="mqtt"
    must replicate writes end-to-end through real MQTT 3.1.1 frames."""
    import os
    import subprocess
    import sys

    from merklekv_tpu.client import MerkleKVClient

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # Server processes run on the CPU backend, like the test process.
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    procs = []

    def spawn(args):
        p = subprocess.Popen(
            [sys.executable, *args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        procs.append(p)
        return p

    try:
        broker = spawn(["-m", "merklekv_tpu.broker", "--port", "0",
                        "--protocol", "mqtt"])
        line = broker.stdout.readline()
        assert "(mqtt) listening on" in line, line
        broker_port = int(line.rsplit(":", 1)[1].split()[0])

        ports = []
        for i in (1, 2):
            cfg = tmp_path / f"node{i}.toml"
            cfg.write_text(f"""
host = "127.0.0.1"
port = 0
engine = "mem"

[replication]
enabled = true
transport = "mqtt"
mqtt_broker = "127.0.0.1"
mqtt_port = {broker_port}
topic_prefix = "mqtt_itest"
client_id = "mq-node-{i}"
""")
            p = spawn(["-m", "merklekv_tpu", "--config", str(cfg)])
            line = p.stdout.readline()
            assert "listening on" in line, line
            ports.append(int(line.rsplit(":", 1)[1].split()[0]))

        with MerkleKVClient("127.0.0.1", ports[0]) as a, \
             MerkleKVClient("127.0.0.1", ports[1]) as b:
            a.set("mq:x", "from-a")
            b.set("mq:y", "from-b")
            deadline = time.time() + 15
            while time.time() < deadline:
                if b.get("mq:x") == "from-a" and a.get("mq:y") == "from-b":
                    break
                time.sleep(0.1)
            assert b.get("mq:x") == "from-a"
            assert a.get("mq:y") == "from-b"
            a.set("mq:del", "gone")
            deadline = time.time() + 15
            while time.time() < deadline and b.get("mq:del") != "gone":
                time.sleep(0.1)
            assert b.get("mq:del") == "gone"  # SET replicated before DEL
            a.delete("mq:del")
            deadline = time.time() + 15
            while time.time() < deadline and b.get("mq:del") is not None:
                time.sleep(0.1)
            assert b.get("mq:del") is None
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


def test_mqtt_transport_reconnects_after_broker_restart():
    """Broker restart heals the MQTT fabric: the transport re-dials,
    re-handshakes, and RE-SUBSCRIBES (clean-session brokers forget filters)
    — rumqttc behavior (/root/reference/src/replication.rs:148-166)."""
    broker = MqttBroker()
    port = broker.port
    t_pub = MqttTransport(broker.host, port, client_id="rc-pub")
    t_sub = MqttTransport(broker.host, port, client_id="rc-sub")
    got = []
    try:
        t_sub.subscribe("mrc/events", lambda topic, p: got.append(p))
        time.sleep(0.05)
        t_pub.publish("mrc/events", b"before")
        assert wait_for(lambda: got == [b"before"])

        broker.close()
        deadline = time.time() + 10
        broker = None
        while time.time() < deadline and broker is None:
            try:
                broker = MqttBroker(port=port)
            except OSError:
                time.sleep(0.1)
        assert broker is not None, "broker could not rebind its port"

        assert wait_for(
            lambda: t_pub.reconnects >= 1 and t_sub.reconnects >= 1,
            timeout=15,
        ), (t_pub.reconnects, t_sub.reconnects)

        # The resubscribed filter must actually deliver.
        deadline = time.time() + 10
        while time.time() < deadline and b"after" not in got:
            t_pub.publish("mrc/events", b"after")
            time.sleep(0.1)
        assert b"after" in got
    finally:
        t_pub.close()
        t_sub.close()
        if broker is not None:
            broker.close()


def test_mqtt_outbox_flushes_after_heal():
    """Events published during a DETECTED broker outage are buffered and
    delivered (after resubscribe) once the link heals."""
    broker = MqttBroker()
    port = broker.port
    t_pub = MqttTransport(broker.host, port, client_id="ob-pub")
    t_sub = MqttTransport(broker.host, port, client_id="ob-sub")
    # The publisher's post-heal drain races the subscriber's resubscribe
    # (QoS-0 has no cross-client ordering); stagger the publisher's first
    # retry so the subscriber deterministically heals first.
    t_pub._BACKOFF_FIRST = 1.5
    got = []
    try:
        t_sub.subscribe("mob/events", lambda topic, p: got.append(p))
        time.sleep(0.05)
        broker.close()
        assert wait_for(lambda: t_pub.link_down and t_sub.link_down), (
            t_pub.link_down, t_sub.link_down
        )
        for i in range(5):
            t_pub.publish("mob/events", b"d-%d" % i)
        assert got == []
        deadline = time.time() + 10
        broker = None
        while time.time() < deadline and broker is None:
            try:
                broker = MqttBroker(port=port)
            except OSError:
                time.sleep(0.1)
        assert broker is not None, "broker could not rebind its port"
        assert wait_for(lambda: len(got) >= 5, timeout=15), got
        assert got == [b"d-%d" % i for i in range(5)]
    finally:
        t_pub.close()
        t_sub.close()
        if broker is not None:
            broker.close()
