"""Open-loop key-value traffic against node ``a`` (YCSB core workloads).

Set-up: the keyspace is loaded and every mirror warmed; every leaf-hash
and scatter program a pump batch can need is made ready
(``common.warm_programs``); bursts of rewrites of ``warm_bursts`` keys
each, every one followed by a settle, run the whole write path; then the
cell's own traffic runs for ``warm_seconds`` and flows on into the window
without a pause.

Window: Poisson arrivals at the cell's rate over ``processes`` x
``per_process`` connections from out-of-process workers (``loadgen.py``),
each request timed from its due time; the traffic runs on for
``tail_seconds`` past the window. A probe on its own connection to ``a``
measures how stale the served root is (``_probe``).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import loadgen
from benchmark.data import conn_of_key, open_loop
from benchmark.drivers.common import (
    check_state,
    nearest_rank,
    prepare,
    scatter_count,
    snapshot_counters,
    wait_scattered,
    warm_programs,
)
from benchmark.nodes import CheckFailure, Wire
from benchmark.reference import Model


def setup(ctx) -> None:
    warm_shapes(ctx)
    start_traffic(ctx, getattr(ctx.args, "rate", None)
                  or ctx.traffic["rate_ops_s"], 0)


def warm_shapes(ctx) -> None:
    tr = ctx.traffic
    # The probe's key is in the tree before the warm build, so its writes
    # are updates in place like the traffic's.
    prepare(ctx, {Model.PROBE_KEY: b"0"})
    ds, model, a = ctx.ds, ctx.model, ctx.cluster["a"]
    t = time.monotonic()
    ctx.readings["warm_programs"] = warm_programs(
        ctx, {Model.PROBE_KEY: b"%d" % 10**6})
    ctx.phases["program_warm_s"] = time.monotonic() - t
    t = time.monotonic()
    rng = np.random.default_rng([ctx.seed, 7])
    replicas = int(ctx.config.get("device_replicas", 1))
    for size in tr["warm_bursts"]:
        # Rewrites of current values: the data stays as it was, and the
        # whole write path (replication, both pumps) runs once.
        idx = rng.choice(ds.n, min(size, ds.n), replace=False)
        before = scatter_count(ctx)
        a.load(ds, idx, model.versions[idx])
        wait_scattered(ctx, before, len(idx) * replicas)
    ctx.cluster.settle()
    ctx.phases["shape_warm_s"] = time.monotonic() - t


def start_traffic(ctx, rate: float, stream: int) -> None:
    """Start the load workers and the probe on ``warm_seconds`` of traffic
    that flows into the window; return at the window's start."""
    tr, ds, a = ctx.traffic, ctx.ds, ctx.cluster["a"]
    t = time.monotonic()
    warm_s = float(tr["warm_seconds"])
    versions = ctx.model.versions.copy()
    # Traffic runs on past the window while the last probes finish.
    sched = open_loop(ds.n, tr, ctx.seed, stream, -warm_s,
                      ctx.seconds + float(tr["tail_seconds"]), rate, versions)
    conns = tr["processes"] * tr["per_process"]
    gen = loadgen.LoadGenerator(
        a.port, ctx.config, ctx.seed, sched, conn_of_key(sched.key, conns),
        tr["processes"], tr["per_process"])
    try:
        gen.wait_ready()
    except BaseException:
        gen.close()
        raise
    ctx.readings.update(sched=sched, gen=gen, rate=rate)
    ctx.phases["loadgen_start_s"] = time.monotonic() - t
    t0 = time.monotonic() + warm_s + 0.05
    gen.start(t0, t0 + ctx.seconds + 60.0)
    ctx.phases["traffic_warm_s"] = warm_s
    # The probe runs through the traffic's warm-up too; only the probes
    # that start in the window count.
    ctx.t0 = t0
    probe: dict = {}
    thread = threading.Thread(target=_probe,
                              args=(ctx, a.port, t0 - warm_s, probe))
    thread.start()
    ctx.readings.update(probe=probe, probe_thread=thread)
    if ctx.trace:
        time.sleep(max(0.0, t0 - 0.5 - time.monotonic()))
        snapshot_counters(ctx, "start")
    time.sleep(max(0.0, t0 - time.monotonic()))


def _probe(ctx, port: int, start: float, out: dict) -> None:
    """Tree lag as a writer sees it: every ``probe_interval_s`` the probe
    writes its own key on ``a``, takes the engine version a stamped HASH
    then reports (served version + lag) as the target, and repeats HASH
    every ``probe_poll_s`` until the served tree's version reaches it. The
    lag is the time from the write's acknowledgement to that answer."""
    import jax

    tr = ctx.traffic
    every, poll = float(tr["probe_interval_s"]), float(tr["probe_poll_s"])
    wire = Wire(port)
    lags, unstamped = [], 0
    key = ctx.model.PROBE_KEY
    nxt, seq, end = start, 0, ctx.t0 + ctx.seconds
    try:
        while nxt < end:
            counted = nxt >= ctx.t0
            time.sleep(max(0.0, nxt - time.monotonic()))
            seq += 1
            with jax.profiler.TraceAnnotation("benchmark.probe"):
                value = b"%d" % seq
                if wire.call(b"SET " + key + b" " + value) != b"OK":
                    raise CheckFailure("the probe's SET failed")
                ctx.model.extra[key] = value
                t1 = time.monotonic()
                st = wire.stamped_hash()
                target = None if st is None else st[1] + st[2]
                while st is not None and st[1] < target:
                    time.sleep(poll)
                    st = wire.stamped_hash()
                if st is None:
                    unstamped += 1
                elif counted:
                    lags.append((time.monotonic() - t1) * 1000.0)
            nxt += every
    finally:
        wire.close()
        out.update(lags=lags, unstamped=unstamped)


def window(ctx) -> dict:
    end = ctx.t0 + ctx.seconds
    ctx.readings["probe_thread"].join()
    probe = ctx.readings["probe"]
    time.sleep(max(0.0, end - time.monotonic()))
    if ctx.trace:
        snapshot_counters(ctx, "end")
    sched, gen = ctx.readings["sched"], ctx.readings["gen"]
    t = time.monotonic()
    res = gen.collect(timeout=ctx.seconds + 180.0)
    ctx.phases["collect_s"] = time.monotonic() - t
    ctx.readings["results"] = res
    in_win = (sched.due >= 0) & (sched.due < ctx.seconds)
    due = ctx.t0 + sched.due
    horizon = ctx.t0 + ctx.seconds + 60.0
    answered = res["status"] == loadgen.OK
    answered |= res["status"] == loadgen.WRONG
    # A request that failed or never came counts as taking until the
    # generator gave up on it.
    lat = np.where(answered, res["done"] - due, horizon - due) * 1000.0
    reads = in_win & (sched.kind == 0)
    writes = in_win & (sched.kind == 1)
    ctx.attempted = int(in_win.sum())
    ctx.failed = int((in_win & ~answered).sum())
    late = (res["sent"][in_win] - due[in_win]) * 1000.0
    ctx.readings["late_p99_ms"] = nearest_rank(late[~np.isnan(late)], 0.99)
    ctx.readings["achieved_ops_s"] = float(
        (in_win & answered).sum() / ctx.seconds)
    if not probe["lags"]:
        raise RuntimeError("the tree-lag probe got no stamped answer")
    return {
        "read_p99_ms": nearest_rank(lat[reads], 0.99),
        "write_p99_ms": nearest_rank(lat[writes], 0.99),
        "tree_lag_p95_ms": nearest_rank(probe["lags"], 0.95),
    }


def absorb(ctx) -> np.ndarray:
    """Apply the last traffic's acknowledged SETs to the model; returns
    the keys they wrote."""
    sched, res = ctx.readings["sched"], ctx.readings["results"]
    ok_set = (sched.kind == 1) & (res["status"] == loadgen.OK)
    np.maximum.at(ctx.model.versions, sched.key[ok_set], sched.ver[ok_set])
    return np.unique(sched.key[ok_set])


def check(ctx) -> None:
    written = absorb(ctx)
    res = ctx.readings["results"]
    wrong = int((res["status"] == loadgen.WRONG).sum())
    for w in res["wrong"][:5]:
        print(f"check: wrong GET answer key={w[0]} version={w[1]} "
              f"got {w[2]!r}", flush=True)
    ctx.check("wrong_get_answers", wrong, 0)
    ctx.check("unanswered_requests",
              int((res["status"] == loadgen.MISSING).sum()), 0)
    ctx.check("probe_unstamped", ctx.readings["probe"]["unstamped"], 0)
    check_state(ctx, written)
