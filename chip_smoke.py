"""Chip smoke: drive the served path once on a TPU and check every answer.

    python chip_smoke.py              # one chip: two nodes, 1,048,576 records
    python chip_smoke.py --chips 4    # four chips: the sharded serving tree

One chip (the default). Two nodes start the way ``python -m merklekv_tpu``
starts them (``[replication] enabled``, ``mqtt_broker = "local"``,
``[device] sharding = auto``). Node A is loaded over TCP, pipelined, with
1,048,576 YCSB CoreWorkload records (key ``user<n>``, 10 fields x 100 B;
Cooper et al., SoCC 2010) plus a few 4 KB / 64 KB / 1 MiB values. The
first HASH warms A's device mirror with one full build over that keyspace.
Then: GETs of a sample, 50,000 pipelined overwrites through the pump's
scatter path, and a bisection SYNC of node B (A's data with 1% of the keys
changed and 100 deleted, drawn at random; B's device mirror warm) served
from A's device tree over TREELEVEL, its repairs staged into B's mirror
page by page. Every HASH is checked against an independent hashlib chain
computed here.

Four chips (``--chips 4``). One node on the four local chips, same data:
the leaf level must be spread over the four devices, HASH and every
TREELEVEL level must equal the hashlib chain and a single-device build of
the same keyspace, and an 8-replica diff through ``merkle/diff.py`` on the
mesh must count what a numpy golden counts.

Either way the device must have done the work: the platform is ``tpu``,
the Pallas kernels ran compiled, the serving tree sits on the top rung of
the degradation ladder with no fallback and no ``device_degraded`` flight
event. Any failure exits non-zero without a result line; on success the
last line of stdout is ``{"ok": true, "device": {...}}``. Without an
accelerator (e.g. ``JAX_PLATFORMS=cpu``) it exits non-zero at once.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import struct
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

RECORDS = 1 << 20
FIELDS, FIELD_BYTES = 10, 100  # YCSB CoreWorkload fieldcount x fieldlength
LARGE_VALUES = (4096, 4096, 65536, 65536, 1 << 20, 1 << 20)
OVERWRITES = 50_000
DEADLINE_S = 1100.0  # the whole run, compilation included
PIPELINE = 2000  # commands per pipelined batch
TREELEVEL_PAGE = 6144  # nodes per TREELEVEL request (fits the 512 KiB reply)
_ALPHABET = np.frombuffer(
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789", np.uint8
)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ timing

class CompileClock:
    """Seconds of XLA backend compilation (summed over threads, so a phase
    whose warm and pump threads compile at once can show more compile than
    wall time), from jax.monitoring — each phase shows compile time apart
    from run time. Tracing and lowering are not counted."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self) -> None:
        import jax

        self._mu = threading.Lock()
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in self._EVENTS:
            with self._mu:
                self.total += duration


class Phase:
    def __init__(self, clock: CompileClock, name: str) -> None:
        self.clock, self.name, self.fields = clock, name, {}

    def __enter__(self) -> "Phase":
        log(f"phase {self.name}: start")
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        wall = time.perf_counter() - self.t0
        comp = self.clock.total - self.c0
        status = "ok" if exc_type is None else f"FAILED ({exc_type.__name__})"
        extra = "".join(f" {k}={v}" for k, v in self.fields.items())
        log(f"phase {self.name}: {status} wall_s={wall:.3f} "
            f"compile_s={comp:.3f} run_s={max(0.0, wall - comp):.3f}{extra}")


# -------------------------------------------------------- reference chain

def leaf(k: bytes, v: bytes) -> bytes:
    return hashlib.sha256(
        struct.pack(">I", len(k)) + k + struct.pack(">I", len(v)) + v
    ).digest()


def chain_levels(leaves: list[bytes]) -> list[list[bytes]]:
    """Every level of the reference tree over leaf hashes in key order:
    sha256(l + r) parents, an odd tail promoted unchanged."""
    levels = [leaves]
    cur = leaves
    while len(cur) > 1:
        nxt = [hashlib.sha256(cur[i] + cur[i + 1]).digest()
               for i in range(0, len(cur) - 1, 2)]
        if len(cur) % 2:
            nxt.append(cur[-1])
        levels.append(nxt)
        cur = nxt
    return levels


class Keyspace:
    """The data a node should hold, generated from a seed: YCSB records in
    a numpy array plus a few large values, and the hashlib chain over it."""

    def __init__(self, n: int, seed: int) -> None:
        self.rng = np.random.default_rng(seed)
        self.keys = [b"user%d" % i for i in range(n + len(LARGE_VALUES))]
        self.records = self.random_rows(n)
        self.large = {
            n + j: _ALPHABET[
                self.rng.integers(0, len(_ALPHABET), size, dtype=np.uint8)
            ].tobytes()
            for j, size in enumerate(LARGE_VALUES)
        }
        self.deleted: set[int] = set()

    def random_rows(self, m: int) -> np.ndarray:
        idx = self.rng.integers(
            0, len(_ALPHABET), (m, FIELDS * FIELD_BYTES), dtype=np.uint8
        )
        return _ALPHABET[idx]

    def value(self, i: int) -> bytes:
        if i in self.large:
            return self.large[i]
        return self.records[i].tobytes()

    def live(self) -> list[int]:
        return [i for i in range(len(self.keys)) if i not in self.deleted]

    def copy(self) -> "Keyspace":
        other = object.__new__(Keyspace)
        other.rng = self.rng
        other.keys = self.keys
        other.records = self.records.copy()
        other.large = dict(self.large)
        other.deleted = set(self.deleted)
        return other

    def levels(self) -> list[list[bytes]]:
        order = sorted(self.live(), key=self.keys.__getitem__)
        return chain_levels([leaf(self.keys[i], self.value(i)) for i in order])

    def root_hex(self) -> str:
        return self.levels()[-1][0].hex()


# ------------------------------------------------------------- the nodes

def start(name: str):
    from merklekv_tpu.__main__ import start_node
    from merklekv_tpu.config import Config

    cfg = Config.from_dict({
        "host": "127.0.0.1",
        "port": 0,
        "server": {"max_line_bytes": 4 << 20},
        "replication": {
            "enabled": True,
            "mqtt_broker": "local",
            # One topic per node: A's writes must not replicate into B,
            # whose divergence the SYNC phase repairs.
            "topic_prefix": f"chip_smoke_{name}",
            "client_id": name,
        },
        "device": {"sharding": "auto"},
    })
    return start_node(cfg)


def client(served):
    from merklekv_tpu.client import MerkleKVClient

    return MerkleKVClient(
        "127.0.0.1", served.server.port, timeout=600.0,
        max_value_bytes=2 << 20,
    ).connect()


def load(c, ks: Keyspace, indices) -> int:
    """Pipelined SETs of ``indices`` over TCP; returns bytes sent."""
    sent = c.bytes_sent
    idx = list(indices)
    for lo in range(0, len(idx), PIPELINE):
        batch = idx[lo : lo + PIPELINE]
        cmds = [
            f"SET {ks.keys[i].decode()} {ks.value(i).decode()}" for i in batch
        ]
        replies = c.pipeline(cmds)
        check(all(r == "OK" for r in replies),
              f"SET answered {set(replies) - {'OK'}}")
    return c.bytes_sent - sent


def metrics(c) -> dict[str, int]:
    out = {}
    for k, v in c.metrics().items():
        try:
            out[k] = int(v)
        except ValueError:
            pass
    return out


def wait_events_drained(c, served, timeout_s: float = 300.0) -> None:
    """Until the native change-event queue is empty and the replicator has
    handed its last drained batch to the mirror (its flush lock orders
    this call after any drain in flight)."""
    end = time.monotonic() + timeout_s
    while int(c.stats().get("events_queue_depth", "0") or 0) > 0:
        check(time.monotonic() < end, "event queue never drained")
        time.sleep(0.2)
    served.node._replicator.flush()


def wait_tree_current(c, timeout_s: float) -> dict[str, int]:
    """Until the device tree is built and published at the live engine
    version; returns that METRICS snapshot."""
    end = time.monotonic() + timeout_s
    c.hash()  # the first root query warms the mirror
    while True:
        m = metrics(c)
        if (
            m.get("device.backend_level", -1) >= 1
            and "device.tree_version" in m
            and m["device.tree_version"] >= m.get("node.engine_version", 0)
        ):
            return m
        check(time.monotonic() < end,
              f"device tree not current after {timeout_s:.0f}s: "
              f"backend_level={m.get('device.backend_level')} "
              f"tree_version={m.get('device.tree_version')} "
              f"engine_version={m.get('node.engine_version')}")
        time.sleep(0.25)


def device_proof(c, served, top_rung: int) -> dict[str, int]:
    """Fail unless the device did the work on this node: platform, compiled
    Pallas, the top ladder rung, no fallback, no degradation event."""
    import jax

    from merklekv_tpu.ops import dispatch

    check(jax.devices()[0].platform == "tpu", "JAX platform is not tpu")
    check(dispatch.use_pallas(), "SHA-256 dispatch is not on Pallas")
    m = metrics(c)
    check(m.get("device.backend_level") == top_rung,
          f"device.backend_level={m.get('device.backend_level')}, "
          f"top rung is {top_rung}")
    for name in ("device.fallbacks", "device.degraded_total",
                 "device.pallas_interpreted", "device.guard_timeouts",
                 "device.pump_errors"):
        check(m.get(name, 0) == 0, f"{name}={m.get(name)}")
    events = [e.get("kind") for e in c.flight(4096)]
    check("device_degraded" not in events, "FLIGHT holds device_degraded")
    check(served.node._mirror.state is not None, "mirror not built")
    return m


def check_root(c, served, ks: Keyspace, what: str) -> str:
    """HASH over the wire, and the root of the node's device tree, must
    both equal the hashlib chain (a native-fallback answer alone would
    pass the first check)."""
    expect = ks.root_hex()
    got = c.hash()
    check(got == expect, f"{what}: HASH {got} != hashlib chain {expect}")
    dev = served.node.device_root_hex()
    check(dev == expect, f"{what}: device tree root {dev} != chain {expect}")
    return got


# ----------------------------------------------------------- one chip run

def run_one_chip(clock: CompileClock, n: int, seed: int, overwrites: int):
    import jax

    from merklekv_tpu.parallel.sharded_state import resolve_shard_count

    top = max(1, resolve_shard_count("auto", len(jax.local_devices())))
    with Phase(clock, "generate") as ph:
        ks_a = Keyspace(n, seed)
        ph.fields.update(records=n, large=len(LARGE_VALUES))

    with Phase(clock, "start_nodes") as ph:
        a, b = start("a"), start("b")
        ca, cb = client(a), client(b)
        ph.fields.update(port_a=a.server.port, port_b=b.server.port)
    try:
        with Phase(clock, "load_a") as ph:
            sent = load(ca, ks_a, range(len(ks_a.keys)))
            wait_events_drained(ca, a)
            ph.fields.update(keys=ca.dbsize(), bytes=sent)
            check(ca.dbsize() == len(ks_a.keys), "A dbsize")

        with Phase(clock, "full_build_a") as ph:
            m = wait_tree_current(ca, 600.0)
            ph.fields.update(leaves=a.node._mirror.leaf_count(),
                             shards=m.get("device.shards"),
                             backend_level=m.get("device.backend_level"))
        with Phase(clock, "hash_a") as ph:
            got = check_root(ca, a, ks_a, "A after load")
            device_proof(ca, a, top)
            ph.fields.update(root=got)

        with Phase(clock, "get_sample") as ph:
            sample = list(ks_a.rng.choice(n, 1000, replace=False)) + list(
                ks_a.large)
            for i in sample:
                got = ca.get(ks_a.keys[i].decode())
                check(got is not None and got.encode() == ks_a.value(int(i)),
                      f"GET {ks_a.keys[i]!r}")
            ph.fields.update(gets=len(sample))

        with Phase(clock, "overwrite_burst_a") as ph:
            m0 = metrics(ca)
            hot = ks_a.rng.choice(n, overwrites, replace=False)
            ks_a.records[hot] = ks_a.random_rows(len(hot))
            sent = load(ca, ks_a, hot)
            wait_events_drained(ca, a)
            wait_tree_current(ca, 600.0)
            m = metrics(ca)  # read after the publish: counters include it
            scattered = (m.get("device.scatter_keys", 0)
                         - m0.get("device.scatter_keys", 0))
            check(scattered >= overwrites,
                  f"pump scattered {scattered} keys < {overwrites}")
            got = check_root(ca, a, ks_a, "A after the overwrite burst")
            device_proof(ca, a, top)
            ph.fields.update(overwrites=len(hot), bytes=sent,
                             scatter_keys=scattered,
                             pump_batches=m.get("device.pump_batches", 0)
                             - m0.get("device.pump_batches", 0),
                             root=got)

        with Phase(clock, "load_b") as ph:
            # B: A's data with 1% of the keys changed and 100 deleted, both
            # drawn uniformly — a replica that missed scattered updates.
            # The deletions shift every leaf position to their right, so
            # the walk repairs most of the keyspace as one paged range.
            ks_b = ks_a.copy()
            pick = ks_a.rng.choice(n, n // 100 + 100, replace=False)
            changed, gone = pick[: n // 100], pick[n // 100 :]
            ks_b.records[changed] = ks_a.random_rows(len(changed))
            ks_b.deleted = set(gone.tolist())
            sent = load(cb, ks_b, ks_b.live())
            wait_events_drained(cb, b)
            check(cb.dbsize() == len(ks_b.live()), "B dbsize")
            ph.fields.update(keys=cb.dbsize(), bytes=sent,
                             changed=len(changed), deleted=len(gone))

        with Phase(clock, "full_build_b") as ph:
            wait_tree_current(cb, 600.0)
            got = check_root(cb, b, ks_b, "B before SYNC")
            device_proof(cb, b, top)
            ph.fields.update(leaves=b.node._mirror.leaf_count(), root=got)

        with Phase(clock, "sync_b_from_a") as ph:
            # B's mirror is warm: each repair page is staged into it in one
            # batch while its pump keeps the device tree current. One SYNC
            # is one anti-entropy cycle, bounded by the cycle deadline; a
            # cycle that runs out checkpoints and the next SYNC resumes it,
            # as the anti-entropy loop would.
            want = ks_a.root_hex()
            m0 = metrics(cb)
            cycles, repaired, wire = 0, 0, 0
            while True:
                check(cb.sync_with("127.0.0.1", a.server.port), "SYNC failed")
                report = b.node.sync_manager.last_report
                if cycles == 0:
                    check(report.mode == "bisect",
                          f"SYNC mode {report.mode}, not bisect")
                cycles += 1
                repaired += report.set_keys + report.deleted_keys
                wire += report.bytes_received + report.bytes_sent
                if b.engine.merkle_root().hex() == want:
                    break
                check(cycles < 8, "B did not converge in 8 SYNC cycles")
            check(repaired >= len(changed) + len(gone),
                  f"SYNC repaired {repaired} keys")
            wait_events_drained(cb, b)
            check(cb.dbsize() == len(ks_a.keys), "B dbsize after SYNC")
            m = wait_tree_current(cb, 600.0)
            ph.fields.update(cycles=cycles, repaired=repaired,
                             wire_bytes=wire, root=want,
                             pump_batches=m.get("device.pump_batches", 0)
                             - m0.get("device.pump_batches", 0))

        with Phase(clock, "hash_after_sync") as ph:
            got_b = check_root(cb, b, ks_a, "B after SYNC")
            got_a = check_root(ca, a, ks_a, "A after SYNC")
            device_proof(ca, a, top)
            device_proof(cb, b, top)
            ph.fields.update(leaves=b.node._mirror.leaf_count(),
                             root_a=got_a, root_b=got_b)
    finally:
        for c in (ca, cb):
            c.close()
        for s in (a, b):
            s.close()


# --------------------------------------------------------- four chip run

def run_four_chips(clock: CompileClock, n: int, seed: int):
    import jax

    from merklekv_tpu.merkle.diff import divergence_masks_engine
    from merklekv_tpu.merkle.incremental import DeviceMerkleState

    check(len(jax.local_devices()) >= 4,
          f"--chips 4 needs 4 local devices, found {len(jax.local_devices())}")
    with Phase(clock, "generate") as ph:
        ks = Keyspace(n, seed)
        ph.fields.update(records=n, large=len(LARGE_VALUES))
    with Phase(clock, "chain") as ph:
        levels = ks.levels()
        ph.fields.update(root=levels[-1][0].hex(), height=len(levels))

    with Phase(clock, "diff_8_replicas") as ph:
        # Key axis a multiple of the mesh: the sharded program's masks come
        # back unsliced, so their placement shows where it ran.
        m_keys = len(levels[0]) // 4 * 4
        base = np.frombuffer(b"".join(levels[0][:m_keys]), ">u4").astype(
            np.uint32).reshape(-1, 8)
        r = 8
        dig = np.repeat(base[None], r, axis=0)
        pres = np.ones((r, m_keys), bool)
        expect = [0]
        for rep in range(1, r):
            flip = ks.rng.choice(m_keys, 1000 * rep, replace=False)
            dig[rep, flip[: 500 * rep], 0] ^= np.uint32(1)
            pres[rep, flip[500 * rep :]] = False
            expect.append(1000 * rep)
        masks = divergence_masks_engine(dig, pres, min_keys=0)
        check(len(masks.sharding.device_set) == 4,
              f"diff ran on {len(masks.sharding.device_set)} devices")
        counts = np.asarray(masks).sum(axis=1).tolist()
        golden = ((pres != pres[:1])
                  | (pres & pres[:1] & (dig != dig[:1]).any(-1)))
        check(counts == expect == golden.sum(axis=1).tolist(),
              f"diff counts {counts} != golden {expect}")
        ph.fields.update(replicas=r, keys=m_keys, counts=counts)
        del dig, pres, masks, golden

    with Phase(clock, "start_node"):
        a = start("a")
        ca = client(a)
    try:
        with Phase(clock, "load") as ph:
            sent = load(ca, ks, range(len(ks.keys)))
            wait_events_drained(ca, a)
            ph.fields.update(keys=ca.dbsize(), bytes=sent)
        with Phase(clock, "sharded_build") as ph:
            m = wait_tree_current(ca, 600.0)
            check(m.get("device.shards") == 4,
                  f"device.shards={m.get('device.shards')}")
            st = a.node._mirror.state
            shards = st._levels[0].addressable_shards
            devs = {s.device for s in shards}
            rows = sorted(s.data.shape[0] for s in shards)
            check(len(devs) == 4 and rows == [st._capacity // 4] * 4,
                  f"leaf level spread {len(devs)} devices, rows {rows}")
            device_proof(ca, a, 4)
            ph.fields.update(shards=m["device.shards"],
                             leaf_devices=len(devs), rows_per_device=rows[0])
        with Phase(clock, "hash_and_treelevel") as ph:
            check(ca.hash() == levels[-1][0].hex(), "HASH != hashlib chain")
            nodes = 0
            for lvl, want in enumerate(levels):
                for lo in range(0, len(want), TREELEVEL_PAGE):
                    rows_, leaves_n = ca.tree_level(lvl, lo,
                                                    lo + TREELEVEL_PAGE)
                    check(leaves_n == len(levels[0]), "TREELEVEL leaf count")
                    got = [h for _, h in rows_]
                    check(got == [d.hex() for d in
                                  want[lo : lo + TREELEVEL_PAGE]],
                          f"TREELEVEL {lvl} [{lo}, ...) != hashlib chain")
                    nodes += len(got)
            device_proof(ca, a, 4)
            ph.fields.update(levels=len(levels), nodes=nodes)
        with Phase(clock, "single_device_build") as ph:
            # The same keyspace's leaf digests (checked against hashlib
            # above), reduced into a tree on one device.
            one = DeviceMerkleState()
            one._set_keys(st._keys)
            one._capacity = st._capacity
            one._levels = one._dispatch_build(np.asarray(st._levels[0]))
            check(one.root_hex() == levels[-1][0].hex(),
                  "single-device root != hashlib chain")
            check(len(one._levels) == len(st._levels), "level count")
            for j, (x, y) in enumerate(zip(one._levels, st._levels)):
                check(np.array_equal(np.asarray(x), np.asarray(y)),
                      f"padded level {j}: sharded != single-device")
            ph.fields.update(levels=len(one._levels))
    finally:
        ca.close()
        a.close()


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0, help="data generation seed")
    args = p.parse_args(argv)

    from merklekv_tpu.utils.jaxenv import configure_compile_cache

    cache = configure_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX platform is {dev.platform!r}, not tpu",
              file=sys.stderr)
        return 2

    timer = threading.Timer(DEADLINE_S, lambda: (
        print(f"chip_smoke: deadline {DEADLINE_S:.0f}s exceeded",
              file=sys.stderr, flush=True), os._exit(3)))
    timer.daemon = True
    timer.start()
    t0 = time.perf_counter()
    clock = CompileClock()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile_cache={cache}")
    try:
        from merklekv_tpu import native_bindings

        with Phase(clock, "native_build"):
            native_bindings.ensure_built(force=True)
        if args.chips == 4:
            run_four_chips(clock, RECORDS, args.seed)
        else:
            run_one_chip(clock, RECORDS, args.seed, OVERWRITES)
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    log(f"total: wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.total:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
