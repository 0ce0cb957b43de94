"""The system under test: replicating nodes started through the node's own
entry point, loaded over TCP, their device mirrors warmed and proved.

From the program this takes ``start_node``, ``Config`` and
``MerkleKVClient``; every reading goes over the wire (HASH, METRICS,
STATS, FLIGHT) or through ``node.device_root_hex`` and the engine's root.
The set-up alone reaches further in, to make programs ready
(``drivers/common.warm_programs``).
"""

from __future__ import annotations

import copy
import os
import socket
import threading
import time

import numpy as np

PIPELINE = 2000  # SETs per pipelined load chunk


class CheckFailure(RuntimeError):
    pass


def deep_merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def metrics_numbers(raw: dict) -> dict:
    out = {}
    for k, v in raw.items():
        try:
            out[k] = int(v)
        except (TypeError, ValueError):
            try:
                out[k] = float(v)
            except (TypeError, ValueError):
                pass
    return out


class Wire:
    """A raw line-protocol connection (one command, one reply line)."""

    def __init__(self, port: int, timeout: float = 120.0) -> None:
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = bytearray()

    def lines(self, count: int) -> list[bytes]:
        out = []
        while len(out) < count:
            end = self.buf.find(b"\r\n")
            if end < 0:
                data = self.sock.recv(1 << 20)
                if not data:
                    raise ConnectionError("server closed the connection")
                self.buf += data
                continue
            out.append(bytes(self.buf[:end]))
            del self.buf[:end + 2]
        return out

    def call(self, line: bytes) -> bytes:
        self.sock.sendall(line + b"\r\n")
        return self.lines(1)[0]

    def pipeline(self, payload: bytes, count: int) -> list[bytes]:
        self.sock.sendall(payload)
        return self.lines(count)

    def stamped_hash(self) -> tuple[str, int, int] | None:
        """(root, served version, lag) from ``HASH vs=01``; None while the
        device mirror is not serving."""
        f = self.call(b"HASH vs=01").decode().split(" ")
        if len(f) != 4 or f[0] != "HASH":
            return None
        return f[1], int(f[2]), int(f[3])

    def close(self) -> None:
        self.sock.close()


class Node:
    def __init__(self, name: str, cfg_dict: dict) -> None:
        from merklekv_tpu.__main__ import start_node
        from merklekv_tpu.client import MerkleKVClient
        from merklekv_tpu.config import Config

        self.name = name
        self.served = start_node(Config.from_dict(cfg_dict))
        self.port = self.served.server.port
        self.client = MerkleKVClient("127.0.0.1", self.port, timeout=300.0,
                                     max_value_bytes=2 << 20).connect()
        self.wire = Wire(self.port)

    # -- readings -------------------------------------------------------------
    def metrics(self) -> dict:
        return metrics_numbers(self.client.metrics())

    def stats(self) -> dict:
        return metrics_numbers(self.client.stats())

    def device_root(self) -> str | None:
        return self.served.node.device_root_hex()

    def engine_root(self) -> str | None:
        r = self.served.engine.merkle_root()
        return r.hex() if r is not None else None

    # -- load -----------------------------------------------------------------
    def load(self, ds, idx: np.ndarray, ver: np.ndarray | None = None) -> int:
        """Pipelined SETs of keys ``idx`` at versions ``ver`` (default 0);
        returns the count acknowledged OK."""
        if ver is None:
            ver = np.zeros(len(idx), np.int64)
        ok = 0
        for lo in range(0, len(idx), PIPELINE):
            ii, vv = idx[lo:lo + PIPELINE], ver[lo:lo + PIPELINE]
            vals = ds.values_at(ii, vv)
            payload = b"".join(
                b"SET " + ds.keys[k] + b" " + v + b"\r\n"
                for k, v in zip(ii.tolist(), vals))
            ok += sum(r == b"OK" for r in self.wire.pipeline(payload, len(ii)))
        return ok

    def set_many(self, keys: list[bytes], values: list[bytes]) -> int:
        """Pipelined SETs of explicit values; the count answered OK."""
        payload = b"".join(b"SET " + k + b" " + v + b"\r\n"
                           for k, v in zip(keys, values))
        return sum(r == b"OK" for r in self.wire.pipeline(payload,
                                                          len(keys)))

    def read_back(self, ds, idx: np.ndarray) -> list[bytes]:
        """Pipelined GETs; the raw reply lines in order."""
        out = []
        for lo in range(0, len(idx), PIPELINE):
            ii = idx[lo:lo + PIPELINE].tolist()
            payload = b"".join(b"GET " + ds.keys[k] + b"\r\n" for k in ii)
            out += self.wire.pipeline(payload, len(ii))
        return out

    def wait_drained(self, timeout: float) -> None:
        """Until the native change-event queue is empty."""
        end = time.monotonic() + timeout
        while self.stats().get("events_queue_depth", 0) > 0:
            if time.monotonic() > end:
                raise CheckFailure(f"{self.name}: event queue never drained")
            time.sleep(0.05)

    def wait_tree_current(self, timeout: float) -> str:
        """Until the device mirror serves (METRICS ``device.backend_level``
        >= 1; the native server answers HASH itself when it does not) a
        root equal to the engine's own; returns it. The version stamp
        cannot say this: its watermark may understate coverage until the
        next write drains. Both roots cost a pass over the keyspace (the
        native fallback's HASH and the engine's root), so this polls
        METRICS and asks for them sparingly."""
        end = time.monotonic() + timeout
        kicked = -1e9
        while True:
            level = self.metrics().get("device.backend_level", -1)
            now = time.monotonic()
            if level < 1:
                if now - kicked > 10.0:
                    self.client.hash()  # a mirror not serving starts warming
                    kicked = now
            else:
                st = self.wire.stamped_hash()
                want = self.engine_root()
                if st is not None and st[0] == want:
                    return want
            if now > end:
                raise CheckFailure(
                    f"{self.name}: device root not the engine's after "
                    f"{timeout:.0f}s (backend_level={level})")
            time.sleep(0.2)

    def device_proof(self, top_rung: int) -> list[str]:
        """What says the device did not do the work on this node: a lower
        rung, any fallback or degradation, an interpreted kernel."""
        bad = []
        m = self.metrics()
        if m.get("device.backend_level") != top_rung:
            bad.append(f"{self.name}: device.backend_level="
                       f"{m.get('device.backend_level')} (top {top_rung})")
        for name in ("device.fallbacks", "device.degraded_total",
                     "device.pallas_interpreted", "device.guard_timeouts",
                     "device.pump_errors"):
            if m.get(name, 0):
                bad.append(f"{self.name}: {name}={m[name]}")
        kinds = [e.get("kind") for e in self.client.flight(4096)]
        if "device_degraded" in kinds:
            bad.append(f"{self.name}: FLIGHT holds device_degraded")
        return bad

    def close(self) -> None:
        try:
            self.wire.close()
            self.client.close()
        finally:
            self.served.close()


class Broker:
    """The replication broker as its own process (``python -m
    merklekv_tpu.broker``), as a deployment runs it beside the nodes."""

    def __init__(self) -> None:
        import subprocess
        import sys

        self.proc = subprocess.Popen(
            [sys.executable, "-m", "merklekv_tpu.broker", "--port", "0"],
            stdout=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        line = self.proc.stdout.readline()
        if "listening on" not in line:
            self.close()
            raise RuntimeError(f"broker did not start: {line!r}")
        self.port = int(line.rsplit(":", 1)[1])

    def close(self) -> None:
        import signal
        import subprocess

        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        self.proc.stdout.close()


class Cluster:
    """The configuration's nodes, in one process, on the local devices,
    with a broker process where they replicate to each other."""

    HANDSHAKE = b"__bench_handshake"

    def __init__(self, config: dict) -> None:
        self.config = config
        self.nodes: dict[str, Node] = {}
        self.broker = None
        try:
            base = config["node"]
            if config.get("replicated"):
                self.broker = Broker()
                base = deep_merge(base, {"replication": {
                    "mqtt_broker": "127.0.0.1",
                    "mqtt_port": self.broker.port}})
            for name, over in config["nodes"].items():
                self.nodes[name] = Node(name, deep_merge(base, over))
            if self.broker is not None:
                self._handshake()
        except BaseException:
            self.close()
            raise

    def _handshake(self, timeout: float = 60.0) -> None:
        """Replication is QoS-0: a write published before a peer's
        subscription is live never reaches it. Write a sentinel on each
        node until every other node has it, then delete it everywhere."""
        end = time.monotonic() + timeout
        for name, node in self.nodes.items():
            key = self.HANDSHAKE + name.encode()
            for present in (True, False):
                while True:
                    cmd = (b"SET " + key + b" 1" if present
                           else b"DELETE " + key)
                    node.wire.call(cmd)
                    time.sleep(0.05)
                    seen = [o.wire.call(b"GET " + key).startswith(b"VALUE")
                            for o in self.nodes.values()]
                    if all(s == present for s in seen):
                        break
                    if time.monotonic() > end:
                        raise CheckFailure("replication handshake timed out")

    def __getitem__(self, name: str) -> Node:
        return self.nodes[name]

    def load_all(self, ds, phases: dict) -> None:
        """Each node in ``config["load"]`` gets the whole keyspace at
        version 0, in parallel; then every node holds it."""
        t = time.monotonic()
        names = self.config["load"]
        errs: list[BaseException] = []
        counts = {}

        def one(name: str) -> None:
            try:
                counts[name] = self.nodes[name].load(ds, np.arange(ds.n))
            except BaseException as e:  # re-raised below
                errs.append(e)

        threads = [threading.Thread(target=one, args=(n,)) for n in names]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errs:
            raise errs[0]
        for name in names:
            if counts[name] != ds.n:
                raise CheckFailure(f"{name}: {counts[name]} of {ds.n} "
                                   "loaded SETs answered OK")
        for node in self.nodes.values():
            node.wait_drained(600.0)
        end = time.monotonic() + 600.0
        for node in self.nodes.values():
            while node.client.dbsize() != ds.n:
                if time.monotonic() > end:
                    raise CheckFailure(f"{node.name}: dbsize "
                                       f"{node.client.dbsize()} != {ds.n}")
                time.sleep(0.1)
        phases["load_s"] = time.monotonic() - t

    def warm(self, phases: dict, timeout: float = 900.0) -> None:
        """Every node's first HASH starts its mirror's warm build; wait
        until each serves a current device root."""
        t = time.monotonic()
        for node in self.nodes.values():
            node.client.hash()
        time.sleep(0.5)
        for node in self.nodes.values():
            node.wait_tree_current(timeout)
        phases["warm_s"] = time.monotonic() - t

    def settle(self, timeout: float = 120.0) -> dict[str, str]:
        """Until every node's queue is drained, its tree current, and all
        roots agree where the nodes replicate to each other."""
        end = time.monotonic() + timeout
        for node in self.nodes.values():
            node.wait_drained(timeout)
        roots = {}
        while True:
            for name, node in self.nodes.items():
                roots[name] = node.wait_tree_current(
                    max(1.0, end - time.monotonic()))
            if len(set(roots.values())) == 1 or not self.config.get(
                    "replicated", True) or time.monotonic() > end:
                return roots
            time.sleep(0.05)

    def close(self) -> None:
        for node in self.nodes.values():
            try:
                node.close()
            except Exception:  # closing the rest matters more
                pass
        if self.broker is not None:
            self.broker.close()
