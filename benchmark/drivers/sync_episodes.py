"""Anti-entropy repair episodes under open-loop GETs on both replicas.

One episode, on two replicas ``a`` and ``b`` that share a replication topic:

1. ``REPLICATE disable`` on b: b leaves the topic.
2. One anti-entropy interval of the configuration's SETs on a (rate x
   update share x ``[anti_entropy] interval_seconds``), uniform keys, each
   a new version, pipelined; b misses every one.
3. Until a has published them all, the broker has routed them, and a's
   served root equals the reference's.
4. ``REPLICATE enable`` on b.
5. The divergence is real: b's root differs from a's, and a seeded sample
   of the episode's keys reads its old value on b while a sample of the
   other keys reads its current one. Then until b's rebuilt mirror serves.
6. The clock starts and ``SYNC <a>`` goes to b.
7. b's ``HASH vs=01`` is polled on its own connection; the clock stops at
   the first answer equal to a's root (a takes no writes meanwhile).

Steps 1-5 are not timed. After each episode every node's HASH, device
root and engine root are compared with the reference.

Set-up: the keyspace is loaded and both mirrors warmed; every leaf-hash
shape that this run's episodes can reach, and every scatter bucket, is
made ready; one warm episode runs through the whole path; then
the GETs start and run ``warm_seconds`` before the window.

Window: whole episodes until at least ``--seconds`` have passed and at
least ``min_episodes`` are done (``max_episodes`` at most). GETs arrive
as a Poisson stream at ``rate_ops_s`` over ``processes`` x
``per_process`` connections, half to each replica (``readgen.py``), and
are checked against each replica's timeline of versions.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark import readgen
from benchmark.data import Dataset, _M64
from benchmark.drivers.common import (
    check_state,
    prepare,
    snapshot_counters,
    warm_scatter,
)
from benchmark.nodes import CheckFailure, Wire
from benchmark.reference import chain_levels, leaf
from benchmark.spec import HERE

TRACES_ITSELF = True  # the traced run traces one whole episode
WARM_EPISODES = 1  # in set-up: readies what the repair path reaches
TRACED_EPISODE = 1  # the window's second: its first follows the warm-up
POLL_S = 0.005  # between b's HASH polls during a repair
DIVERGENCE_SAMPLE = 2048  # keys read back on b before each SYNC, each side
ROUTED_QUIET_POLLS = 3  # 0.1 s polls with a's frame count unmoved


# ------------------------------------------------------------------ plan

def divergence_size(ctx) -> int:
    """SETs one replica misses in one anti-entropy interval."""
    cfg = ctx.config
    interval = float(cfg["node"]["anti_entropy"]["interval_seconds"])
    return int(round(float(ctx.traffic["rate_ops_s"])
                     * float(cfg["update_proportion"]) * interval))


def plan_episodes(ds: Dataset, versions: np.ndarray, count: int, size: int,
                  seed: int) -> list[dict]:
    """Each episode's SETs from the seed: uniform keys, each SET the next
    version of its key. ``versions`` is not changed."""
    v = versions.copy()
    out = []
    for e in range(count):
        rng = np.random.default_rng([seed & _M64, 200 + e])
        key = rng.integers(0, ds.n, size, dtype=np.int64)
        ver = np.empty(size, np.int64)
        # The j-th SET of a key in the episode writes v[key] + j.
        order = np.argsort(key, kind="stable")
        ks = key[order]
        start = np.r_[True, ks[1:] != ks[:-1]]
        first = np.flatnonzero(start)
        grp = np.cumsum(start) - 1
        occ = np.arange(size) - first[grp] + 1
        ver[order] = v[ks] + occ
        last = np.r_[first[1:] - 1, size - 1]
        v[ks[last]] += occ[last]
        out.append({"key": key, "ver": ver, "distinct": ks[first]})
    return out


def packed_shapes(keys: list[bytes], values: list[bytes]) -> set:
    """(rows, width) of every group the program packs these leaves into."""
    from merklekv_tpu.merkle.packing import pack_leaf_groups

    return {(p.n, p.max_blocks) for _, p in pack_leaf_groups(keys, values)}


def reachable_shapes(ds: Dataset, versions: np.ndarray,
                     plan: list[dict]) -> tuple[set, set]:
    """Leaf-hash shapes the run can reach, as (whole-keyspace, batch):
    the keyspace as it stands before each episode (a rejoining replica's
    rebuild and its SYNC's leaf map), and any batch of the episode's SETs
    (the pumps', the repair's): every power of two of rows up to the
    largest group of each width."""
    idx = np.arange(ds.n)
    v = versions.copy()
    whole, batch = set(), set()
    for ep in plan:
        whole |= packed_shapes(ds.keys, ds.values_at(idx, v))
        keys = [ds.keys[k] for k in ep["key"].tolist()]
        for rows, w in packed_shapes(keys, ds.values_at(ep["key"],
                                                        ep["ver"])):
            batch |= {(1 << i, w) for i in range(rows.bit_length())}
        np.maximum.at(v, ep["key"], ep["ver"])
    return whole, batch


def warm_leaf_shapes(shapes: set, label) -> None:
    """One leaf hash of each (rows, width): rows leaves whose messages
    fill exactly ``width`` SHA-256 blocks."""
    from merklekv_tpu.merkle.jax_engine import leaf_digests

    for rows, w in sorted(shapes):
        key = b"w"
        value = b"x" * (64 * w - 17 - len(key))
        leaf_digests([key] * rows, [value] * rows, label=label)


def guarded_leaf_shapes() -> set:
    """(rows, width) of every leaf-hash program the dispatch guard holds."""
    from merklekv_tpu.device.guard import get_guard
    from merklekv_tpu.merkle.jax_engine import _leaf_fn
    from merklekv_tpu.ops.dispatch import use_pallas

    program = _leaf_fn(use_pallas())
    return {leaves[0][0][:2] for prog, _tree, leaves
            in list(get_guard()._executables) if prog is program}


class RootChain:
    """The reference root over the model, kept up to date leaf by leaf
    (key order never changes: no key is added or removed)."""

    def __init__(self, model) -> None:
        ds = model.ds
        self.model = model
        self.order = sorted(range(ds.n), key=ds.keys.__getitem__)
        self.pos = np.empty(ds.n, np.int64)
        self.pos[self.order] = np.arange(ds.n)
        vals = model.values_of(np.arange(ds.n))
        self.leaves = [leaf(ds.keys[i], vals[i]) for i in self.order]

    def update(self, idx: np.ndarray) -> str:
        ds = self.model.ds
        idx = np.unique(idx)
        for i, v in zip(idx.tolist(), self.model.values_of(idx)):
            self.leaves[self.pos[i]] = leaf(ds.keys[i], v)
        return chain_levels(self.leaves)[-1][0].hex()


# ------------------------------------------------------------------ steps

def _ok(reply: bytes, what: str) -> None:
    if reply != b"OK":
        raise CheckFailure(f"{what}: {reply!r}")


def write_divergence(ctx, ep: dict) -> dict:
    """Pipelined SETs of the episode on a; the time each chunk was sent
    and fully acknowledged (a read may see a SET from the first, must
    from the second)."""
    from benchmark.nodes import PIPELINE

    ds, a = ctx.ds, ctx.cluster["a"]
    key, ver = ep["key"], ep["ver"]
    possible = np.empty(len(key))
    certain = np.empty(len(key))
    ok = 0
    for lo in range(0, len(key), PIPELINE):
        ii, vv = key[lo:lo + PIPELINE], ver[lo:lo + PIPELINE]
        payload = b"".join(b"SET " + ds.keys[k] + b" " + v + b"\r\n"
                           for k, v in zip(ii.tolist(),
                                           ds.values_at(ii, vv)))
        possible[lo:lo + PIPELINE] = time.monotonic()
        ok += sum(r == b"OK" for r in a.wire.pipeline(payload, len(ii)))
        certain[lo:lo + PIPELINE] = time.monotonic()
    if ok != len(key):
        raise CheckFailure(f"{len(key) - ok} of {len(key)} SETs on a "
                           "were not acknowledged")
    np.maximum.at(ctx.model.versions, key, ver)
    return {"key": key, "ver": ver, "possible": possible,
            "certain": certain}


def wait_routed(ctx, published_before: int, sets: int,
                timeout: float = 120.0) -> None:
    """Until a has published every SET of the episode and the broker has
    routed the last frame: a receives its own frames back, so its count
    of applied frames stops moving once the broker is done."""
    a = ctx.cluster["a"]
    end = time.monotonic() + timeout
    stable, last = 0, None
    while True:
        m = a.metrics()
        out = (m.get("replicator.published", 0)
               + m.get("replicator.coalesced", 0) - published_before)
        frames = m.get("span.replication.apply_frame.count", 0)
        if out >= sets and frames == last:
            stable += 1
            if stable >= ROUTED_QUIET_POLLS:
                return
        else:
            stable = 0
        last = frames
        if time.monotonic() > end:
            raise CheckFailure(f"a published {out} of {sets} SETs")
        time.sleep(0.1)


def check_divergence(ctx, ep: dict, before: np.ndarray, target: str) -> int:
    """What b shows on rejoining, before the SYNC: a root other than a's;
    a sample of the episode's keys at their values before the episode and
    a sample of the other keys at their current values. Returns the count
    of what does not hold."""
    ds, b = ctx.ds, ctx.cluster["b"]
    bad = 0
    st = b.wire.stamped_hash()
    if st is None or st[0] == target:
        print(f"check: b's root {st} before the SYNC is a's", flush=True)
        bad += 1
    rng = np.random.default_rng([ctx.seed, 17, ep["index"]])
    want = DIVERGENCE_SAMPLE
    div = ep["distinct"]
    mine = rng.choice(div, min(want, len(div)), replace=False)
    rest = np.setdiff1d(np.arange(ds.n), div, assume_unique=True)
    other = rng.choice(rest, min(want, len(rest)), replace=False)
    for idx, ver, what in ((mine, before[mine], "old"),
                           (other, ctx.model.versions[other], "current")):
        got = b.read_back(ds, idx)
        exp = [b"VALUE " + v for v in ds.values_at(idx, ver)]
        miss = sum(g != e for g, e in zip(got, exp))
        if miss:
            print(f"check: episode {ep['index']}: {miss} of {len(idx)} "
                  f"keys on b not at their {what} value", flush=True)
        bad += miss
    return bad


def timed_sync(ctx, target: str, trace_dir: str | None) -> dict:
    """SYNC b against a; poll b's stamped HASH until it serves ``target``.
    Returns the send, reply and convergence times and the reply."""
    import jax

    tr, a, b = ctx.traffic, ctx.cluster["a"], ctx.cluster["b"]
    timeout = float(tr["repair_timeout_s"])
    poller = Wire(b.port)
    syncer = Wire(b.port, timeout=timeout + 60.0)
    go, out = threading.Event(), {"match": None}

    def poll() -> None:
        go.wait()
        end = out["send"] + timeout
        while time.monotonic() < end:
            st = poller.stamped_hash()
            if st is not None and st[0] == target:
                out["match"] = time.monotonic()
                return
            time.sleep(POLL_S)

    th = threading.Thread(target=poll, name="bench-sync-poll")
    th.start()
    traced = trace_dir is not None
    try:
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            out["trace_from"] = time.monotonic()
        out["send"] = time.monotonic()
        go.set()
        out["reply"] = syncer.call(b"SYNC 127.0.0.1 %d" % a.port)
        out["replied"] = time.monotonic()
        with jax.profiler.TraceAnnotation("benchmark.await_root"):
            th.join()
        if traced:
            out["trace_to"] = time.monotonic()
            jax.profiler.stop_trace()
    finally:
        go.set()
        th.join()
        poller.close()
        syncer.close()
    return out


def check_roots(ctx, target: str) -> int:
    """Every node's HASH, device root and engine root against the
    reference root; the count that differ."""
    bad = 0
    for name, node in ctx.cluster.nodes.items():
        try:
            node.wait_tree_current(float(ctx.traffic["repair_timeout_s"]))
        except CheckFailure as e:
            print(f"check: {e}", flush=True)
        st = node.wire.stamped_hash()
        got = {"hash": st[0] if st else None,
               "device_root": node.device_root(),
               "engine_root": node.engine_root()}
        for what, r in got.items():
            if r != target:
                bad += 1
                print(f"check: {name} {what} {r} != reference {target}",
                      flush=True)
    return bad


def run_episode(ctx, ep: dict, trace_dir: str | None = None) -> dict:
    a, b = ctx.cluster["a"], ctx.cluster["b"]
    c = ctx.counts
    ctx.episode = ep
    t = time.monotonic()
    _ok(b.wire.call(b"REPLICATE disable"), "REPLICATE disable on b")
    before = ctx.model.versions.copy()
    m = a.metrics()
    pub0 = m.get("replicator.published", 0) + m.get("replicator.coalesced", 0)
    writes = write_divergence(ctx, ep)
    t_write = time.monotonic() - t
    target = ctx.chain.update(ep["distinct"])
    wait_routed(ctx, pub0, len(ep["key"]))
    served = a.wait_tree_current(float(ctx.traffic["repair_timeout_s"]))
    if served != target:
        print(f"check: a serves {served}, reference {target}", flush=True)
        c["root_mismatch"] += 1
    _ok(b.wire.call(b"REPLICATE enable"), "REPLICATE enable on b")
    c["divergence_unmet"] += check_divergence(ctx, ep, before, target)
    try:
        b.wait_tree_current(float(ctx.traffic["repair_timeout_s"]))
    except CheckFailure as e:
        print(f"check: {e}", flush=True)
    t_ready = time.monotonic() - t
    sync = timed_sync(ctx, target, trace_dir)
    if sync["reply"] != b"OK":
        print(f"check: SYNC answered {sync['reply']!r}", flush=True)
        c["sync_errors"] += 1
    timeout = float(ctx.traffic["repair_timeout_s"])
    converged = sync["match"] is not None
    repair_s = (sync["match"] - sync["send"]) if converged else timeout
    c["root_mismatch"] += check_roots(ctx, target)
    if trace_dir is not None:
        ctx.trace_window = (sync["trace_from"], sync["trace_to"])
    ctx.timelines["a"].append(writes)
    ctx.timelines["b"].append({
        "key": ep["distinct"], "ver": ctx.model.versions[ep["distinct"]],
        "possible": np.full(len(ep["distinct"]), sync["send"]),
        "certain": np.full(len(ep["distinct"]), sync["replied"])})
    return {"repair_s": repair_s, "reply_s": sync["replied"] - sync["send"],
            "at_s": sync["send"] - (ctx.t0 or sync["send"]),
            "converged": converged, "write_s": t_write, "ready_s": t_ready,
            "total_s": time.monotonic() - t}


# ------------------------------------------------------------------ phases

def setup(ctx) -> None:
    tr = ctx.traffic
    ctx.counts = {"root_mismatch": 0, "divergence_unmet": 0,
                  "sync_errors": 0}
    ctx.timelines = {"a": [], "b": []}
    prepare(ctx)
    ds = ctx.ds
    t = time.monotonic()
    warm_n = WARM_EPISODES
    plan = plan_episodes(ds, ctx.model.versions,
                         warm_n + int(tr["max_episodes"]),
                         divergence_size(ctx), ctx.seed)
    for e, ep in enumerate(plan):
        ep["index"] = e
    ctx.plan = plan
    ctx.chain = RootChain(ctx.model)
    ctx.phases["plan_s"] = time.monotonic() - t
    t = time.monotonic()
    for ep in plan[:warm_n]:
        run_episode(ctx, ep)
    ctx.phases["warm_episodes_s"] = time.monotonic() - t
    t = time.monotonic()
    # What the warm episodes did not make ready: the leaf-hash shapes the
    # later episodes reach (the loaded keyspace's groups were hashed by
    # both warm builds on the guarded path and by the warm episode's SYNC
    # on the plain jit path), and the scatter buckets.
    whole, batch = reachable_shapes(ds, ctx.model.versions, plan[warm_n:])
    loaded = packed_shapes(ds.keys, ds.values_at(np.arange(ds.n),
                                                 np.zeros(ds.n, np.int64)))
    guarded = (whole | batch) - guarded_leaf_shapes()
    warm_leaf_shapes(guarded, "benchmark.warm")
    warm_leaf_shapes(whole - loaded, None)
    ctx.readings["warm_leaf_shapes"] = len(guarded) + len(whole - loaded)
    ctx.readings["warm_scatter"] = warm_scatter(ctx)
    ctx.phases["program_warm_s"] = time.monotonic() - t
    # Reads start from here: what the warm episodes wrote is the base.
    ctx.timelines = {"a": [], "b": []}
    ctx.base_versions = ctx.model.versions.copy()
    t = time.monotonic()
    warm_s = float(tr["warm_seconds"])
    gen = readgen.ReadGenerator(
        [ctx.cluster["a"].port, ctx.cluster["b"].port], ctx.config,
        ctx.seed, float(tr["rate_ops_s"]), int(tr["processes"]),
        int(tr["per_process"]), -warm_s)
    try:
        gen.wait_ready()
    except BaseException:
        gen.close()
        raise
    ctx.readings["gen"] = gen
    ctx.phases["loadgen_start_s"] = time.monotonic() - t
    t0 = time.monotonic() + warm_s + 0.05
    gen.start(t0, float(tr["grace_s"]))
    ctx.phases["traffic_warm_s"] = warm_s
    ctx.t0 = t0
    if ctx.trace:
        time.sleep(max(0.0, t0 - 0.5 - time.monotonic()))
        snapshot_counters(ctx, "start")
    time.sleep(max(0.0, t0 - time.monotonic()))


def window(ctx) -> dict:
    tr = ctx.traffic
    warm_n = WARM_EPISODES
    episodes = []
    trace_at = TRACED_EPISODE if ctx.trace else -1
    trace_dir = os.path.join(HERE, "out", "trace", ctx.cell["name"])
    for ep in ctx.plan[warm_n:]:
        n = len(episodes)
        with_trace = trace_dir if n == trace_at else None
        r = run_episode(ctx, ep, with_trace)
        episodes.append(r)
        if not r["converged"]:
            break  # a repair that never came: the check says the rest
        if (n + 1 >= int(tr["min_episodes"])
                and time.monotonic() - ctx.t0 >= ctx.seconds):
            break
    end = time.monotonic()
    ctx.readings["window_s"] = end - ctx.t0
    if ctx.trace:
        snapshot_counters(ctx, "end")
    gen = ctx.readings["gen"]
    gen.finish(end, float(tr["grace_s"]) + 60.0)
    ctx.readings["episodes"] = len(episodes)
    ctx.readings["episode_list"] = episodes
    for k in ("at_s", "repair_s", "reply_s", "write_s", "ready_s",
              "total_s"):
        vals = [r[k] for r in episodes]
        ctx.readings[f"episode_{k}"] = ",".join(f"{v:.4f}" for v in vals)
    ctx.readings["repair_timeouts"] = sum(not r["converged"]
                                         for r in episodes)
    return {"sync_repair_s": float(np.median([r["repair_s"]
                                              for r in episodes])),
            "sync_reply_s": float(np.median([r["reply_s"]
                                             for r in episodes]))}


def _timeline(parts: list[dict]) -> dict:
    if not parts:
        return {"key": np.zeros(0, np.int64), "ver": np.zeros(0, np.int64),
                "possible": np.zeros(0), "certain": np.zeros(0)}
    tl = {k: np.concatenate([p[k] for p in parts])
          for k in ("key", "ver", "possible", "certain")}
    order = np.lexsort((tl["ver"], tl["possible"], tl["key"]))
    return {k: v[order] for k, v in tl.items()}


def check(ctx) -> None:
    from merklekv_tpu.utils import jaxenv

    tr = ctx.traffic
    gen = ctx.readings["gen"]
    t = time.monotonic()
    res = gen.collect([_timeline(ctx.timelines["a"]),
                       _timeline(ctx.timelines["b"])],
                      ctx.base_versions, float(tr["grace_s"]) + 300.0)
    ctx.phases["collect_s"] = time.monotonic() - t
    in_win = (res["due"] >= ctx.t0) & (res["due"] < ctx.t0
                                        + ctx.readings["window_s"])
    answered = ~np.isnan(res["done"])
    ctx.attempted = int(in_win.sum())
    ctx.failed = int((in_win & ~answered).sum())
    late = (res["sent"] - res["due"])[in_win] * 1000.0
    if len(late):
        ctx.readings["late_p99_ms"] = float(np.percentile(late, 99))
        lat = (res["done"] - res["due"])[in_win & answered] * 1000.0
        ctx.readings["read_p99_ms"] = float(np.percentile(lat, 99))
    ctx.readings["achieved_ops_s"] = float(
        (in_win & answered).sum() / ctx.readings["window_s"])
    for x in res["examples"]:
        print(f"check: wrong GET answer node={'ab'[x[0]]} key={x[1]} "
              f"expected version={x[2]} (+{x[3]}) sent={x[4]:.3f} "
              f"done={x[5]:.3f}", flush=True)
    # Per connection, for the record: node, requests, unanswered, the
    # first unanswered request's due time and the longest wait (s, from
    # the window's start).
    stats = []
    for c in np.unique(res["conn"]).tolist():
        mine = res["conn"] == c
        due, done = res["due"][mine], res["done"][mine]
        lost = np.isnan(done)
        wait = done[~lost] - due[~lost]
        stats.append("%d:%s:%d:%d:%.1f:%.1f" % (
            c, "ab"[int(res["node"][mine][0])], int(mine.sum()),
            int(lost.sum()), due[lost].min() - ctx.t0 if lost.any()
            else -1.0, wait.max() if len(wait) else -1.0))
    ctx.readings["conn_stats"] = " ".join(stats)
    ctx.check("wrong_get_answers", int(res["wrong"].sum()), 0)
    ctx.check("unanswered_requests", int((~answered).sum()), 0)
    c = ctx.counts
    ctx.check("sync_errors", c["sync_errors"], 0)
    ctx.check("divergence_unmet", c["divergence_unmet"], 0)
    written = np.unique(np.concatenate(
        [ep["key"] for ep in ctx.plan[:WARM_EPISODES
                                       + ctx.readings["episodes"]]]))
    check_state(ctx, written)
    end_roots = ctx.checks["root_mismatch"][0]
    ctx.check("root_mismatch", end_roots + c["root_mismatch"], 0)
    if jaxenv.device_failed():
        print("check: the program fell back to host hashing", flush=True)
        value, lim = ctx.checks["device_proof_failures"]
        ctx.check("device_proof_failures", value + 1, lim)
