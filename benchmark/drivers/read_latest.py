"""Open-loop YCSB D traffic against node ``a``: reads of the latest records
and inserts that grow the keyspace (``benchmark/latest.py``).

Set-up: the schedule is drawn first, so the dataset holds every key it
inserts; the loaded keyspace and the probe's key are written and every
mirror warmed; every leaf-hash and scatter program a pump batch can need
is made ready (``common.warm_programs``), and every restructure program a
pump can reach while the tree grows by the schedule's inserts, through the
node's own ``ready_restructure_programs``; bursts of rewrites of ``warm_bursts``
loaded keys each run the whole write path; then the cell's own traffic,
inserts included, runs for ``warm_seconds`` and flows on into the window.

Window and check are the open-loop driver's (``open_loop.window`` and
``open_loop.check``): Poisson arrivals over ``processes`` x
``per_process`` connections, the tree-lag probe on its own connection,
and the reference check, here over the keyspace the acknowledged inserts
grew (``reference_growing.GrowingModel``).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import loadgen
from benchmark.data import conn_of_key
from benchmark.drivers.common import (
    scatter_count,
    snapshot_counters,
    wait_scattered,
    warm_programs,
)
from benchmark.drivers.open_loop import _probe, check, window
from benchmark.latest import GrowingDataset, read_latest
from benchmark.nodes import CheckFailure
from benchmark.reference_growing import GrowingModel

__all__ = ["setup", "window", "check"]


def setup(ctx) -> None:
    from merklekv_tpu.cluster.node import ClusterNode

    if not hasattr(ClusterNode, "ready_restructure_programs"):
        # Without it the first inserts make programs ready in the window.
        raise RuntimeError("the program cannot make its restructure "
                           "programs ready ahead of the window")
    tr = ctx.traffic
    rate = getattr(ctx.args, "rate", None) or tr["rate_ops_s"]
    warm_s = float(tr["warm_seconds"])
    t = time.monotonic()
    n = int(ctx.config["recordcount"])
    # Traffic runs on past the window while the last probes finish.
    sched = read_latest(n, tr, ctx.seed, 0, -warm_s,
                        ctx.seconds + float(tr["tail_seconds"]), rate)
    ctx.ds = GrowingDataset(ctx.config, ctx.seed,
                            int((sched.kind == 1).sum()))
    ctx.model = GrowingModel(ctx.ds)
    ctx.phases["generate_s"] = time.monotonic() - t
    ctx.cluster.load_all(ctx.ds, ctx.phases)
    a = ctx.cluster["a"]
    probe = ctx.model.PROBE_KEY
    # The probe's key is in the tree before the warm build, so its writes
    # are updates in place.
    if a.wire.call(b"SET " + probe + b" 0") != b"OK":
        raise CheckFailure("the probe key's SET failed")
    ctx.model.extra[probe] = b"0"
    ctx.cluster.warm(ctx.phases)
    warm_shapes(ctx)
    start_traffic(ctx, sched, rate)


def warm_shapes(ctx) -> None:
    ds, model, a = ctx.ds, ctx.model, ctx.cluster["a"]
    t = time.monotonic()
    asked = warm_programs(ctx, {model.PROBE_KEY: b"%d" % 10**6})
    # The loaded keys, every key the schedule inserts, and the probe's.
    leaves = int(ds.config["recordcount"]) + 1
    for node in ctx.cluster.nodes.values():
        asked += node.served.node.ready_restructure_programs(leaves)
    ctx.readings["warm_programs"] = asked
    ctx.phases["program_warm_s"] = time.monotonic() - t
    t = time.monotonic()
    rng = np.random.default_rng([ctx.seed, 7])
    replicas = int(ctx.config.get("device_replicas", 1))
    for size in ctx.traffic["warm_bursts"]:
        # Rewrites of loaded records at their current values: the data
        # stays as it was, and the whole write path runs once.
        idx = rng.choice(ds.n, min(size, ds.n), replace=False)
        before = scatter_count(ctx)
        a.load(ds, idx, model.versions[idx])
        wait_scattered(ctx, before, len(idx) * replicas)
    ctx.cluster.settle()
    ctx.phases["shape_warm_s"] = time.monotonic() - t


def start_traffic(ctx, sched, rate: float) -> None:
    """Start the load workers and the probe on ``warm_seconds`` of traffic
    that flows into the window; return at the window's start."""
    tr, a = ctx.traffic, ctx.cluster["a"]
    t = time.monotonic()
    warm_s = float(tr["warm_seconds"])
    conns = tr["processes"] * tr["per_process"]
    gen = loadgen.LoadGenerator(
        a.port, ctx.ds.config, ctx.seed, sched, conn_of_key(sched.key, conns),
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
