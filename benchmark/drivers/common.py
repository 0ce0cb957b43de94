"""Pieces the drivers share: loading, the reference check, percentiles."""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark.data import Dataset
from benchmark.nodes import CheckFailure
from benchmark.reference import Model


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (the value below which a share q of
    the samples lie, itself a sample)."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        raise ValueError("no samples")
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def top_rung() -> int:
    """The ladder's top rung under ``[device] sharding = auto``: the
    largest power of two of local devices (1 on one chip)."""
    import jax

    n = len(jax.local_devices())
    return 1 << (n.bit_length() - 1)


def prepare(ctx, extra: dict | None = None) -> None:
    """Data from the seed, the keyspace loaded (with ``extra`` keys outside
    the dataset written on node ``a``), every mirror warm."""
    t = time.monotonic()
    ctx.ds = Dataset(ctx.config, ctx.seed)
    ctx.model = Model(ctx.ds)
    ctx.phases["generate_s"] = time.monotonic() - t
    ctx.cluster.load_all(ctx.ds, ctx.phases)
    for k, v in (extra or {}).items():
        if ctx.cluster["a"].wire.call(b"SET " + k + b" " + v) != b"OK":
            raise CheckFailure(f"SET {k!r} failed")
        ctx.model.extra[k] = v
    ctx.cluster.warm(ctx.phases)


def wait_scattered(ctx, before: int, keys: int, timeout: float = 300.0):
    """Until the pumps have scattered ``keys`` more leaf digests (the
    registry is the process's: every node's pump counts)."""
    node = next(iter(ctx.cluster.nodes.values()))
    end = time.monotonic() + timeout
    while node.metrics().get("device.scatter_keys", 0) - before < keys:
        if time.monotonic() > end:
            raise CheckFailure(f"{keys} rewrites never reached the trees")
        time.sleep(0.05)


def scatter_count(ctx) -> int:
    node = next(iter(ctx.cluster.nodes.values()))
    return node.metrics().get("device.scatter_keys", 0)


def warm_programs(ctx, extra: dict) -> int:
    """Make ready in set-up every leaf-hash and scatter program a pump can
    reach while the keyspace keeps its size, whatever batch the traffic
    forms; returns how many were asked for.

    A pump batch of k distinct keys hashes them in groups of a power-of-two
    row count (at most 2^20 blocks a group) and scatters them with the
    program of the power of two at or above max(k, 16), up to the tree's
    capacity. The wire cannot make a batch of a chosen size (the pump
    drains while a burst arrives), so this asks the program for each size
    directly: the scatter programs through the dispatch guard's
    compile-ahead (nothing runs), the leaf hashes by hashing that many of
    the dataset's records (a pure function). The tree is not touched.
    ``extra`` holds the keys written beside the dataset (one row each)."""
    import jax.numpy as jnp

    from merklekv_tpu.device.guard import get_guard
    from merklekv_tpu.merkle import incremental
    from merklekv_tpu.merkle.jax_engine import leaf_digests
    from merklekv_tpu.merkle.packing import GROUP_BLOCKS
    from merklekv_tpu.ops.dispatch import use_pallas

    ds, guard = ctx.ds, get_guard()
    asked = 0
    capacities = {}
    for node in ctx.cluster.nodes.values():
        state = node.served.node._mirror._state
        capacities[state._capacity] = state._levels
    for capacity, levels in capacities.items():
        kb = 16
        while kb <= capacity:
            guard.compiled("benchmark.warm", incremental._scatter_fn(
                capacity, kb, use_pallas()), levels,
                jnp.zeros(kb, jnp.int32), jnp.zeros((kb, 8), jnp.uint32))
            asked += 1
            kb *= 2
    # Leaf groups: records of one padded width (the power of two at or
    # above their SHA-256 block count, at their loaded size) hash together,
    # at most GROUP_BLOCKS blocks a group.
    idx = np.arange(ds.n)
    zeros = np.zeros(ds.n, np.int64)
    klen = np.fromiter(map(len, ds.keys), np.int64, ds.n)
    blocks = (8 + klen + ds._size(idx, zeros) + 9 + 63) // 64
    width = 1 << np.ceil(np.log2(blocks)).astype(np.int64)
    for w in np.unique(width).tolist():
        of_w = idx[width == w]
        top = min(len(of_w), GROUP_BLOCKS // w)
        rows = 1
        while rows < 2 * top:
            pick = np.resize(of_w, rows)
            leaf_digests([ds.keys[i] for i in pick.tolist()],
                         ds.values_at(pick, np.zeros(rows, np.int64)),
                         label="benchmark.warm")
            asked += 1
            rows *= 2
    for k, v in extra.items():
        leaf_digests([k], [v], label="benchmark.warm")
        asked += 1
    return asked


def snapshot_counters(ctx, tag: str) -> None:
    """Program counters at the window's edges. The registry behind METRICS
    is the process's, so every node counts into one name; node a's
    answer stands for all."""
    ctx.readings[f"metrics.a.{tag}"] = ctx.cluster["a"].metrics()


def check_state(ctx, written: np.ndarray) -> None:
    """Every node against the reference: HASH over the wire, the device
    tree's root and the engine's root equal the hashlib chain over the
    model; keys written (and a seeded sample of the rest) read back over
    GET; the device did the work."""
    ds, model = ctx.ds, ctx.model
    t = time.monotonic()
    try:
        ctx.cluster.settle(float(ctx.traffic.get("settle_timeout_s", 120)))
    except CheckFailure as e:  # the comparison below counts what is off
        print(f"check: {e}", flush=True)
    want = model.root_hex()
    rng = np.random.default_rng([ctx.seed, 11])
    sample = np.union1d(written, rng.choice(ds.n, min(2048, ds.n),
                                            replace=False))
    expect = [b"VALUE " + v for v in model.values_of(sample)]
    roots_wrong = readback_wrong = proof_bad = 0
    rung = top_rung()
    for name, node in ctx.cluster.nodes.items():
        st = node.wire.stamped_hash()
        got = {"hash": st[0] if st else None,
               "device_root": node.device_root(),
               "engine_root": node.engine_root()}
        for what, r in got.items():
            if r != want:
                roots_wrong += 1
                print(f"check: {name} {what} {r} != reference {want}",
                      flush=True)
        lines = node.read_back(ds, sample)
        bad = [int(sample[i]) for i, (a, b) in enumerate(zip(lines, expect))
               if a != b]
        readback_wrong += len(bad)
        if bad:
            print(f"check: {name} read back {len(bad)} wrong values, "
                  f"first keys {bad[:5]}", flush=True)
        for msg in node.device_proof(rung):
            proof_bad += 1
            print(f"check: {msg}", flush=True)
    ctx.check("root_mismatch", roots_wrong, 0)
    ctx.check("readback_mismatch", readback_wrong, 0)
    ctx.check("device_proof_failures", proof_bad, 0)
    ctx.phases["check_s"] = time.monotonic() - t
