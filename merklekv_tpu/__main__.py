"""CLI entry point: `python -m merklekv_tpu [--config X] [--engine E] ...`.

Flag surface mirrors the reference binary (/root/reference/src/main.rs:61-151):
--config, --engine, --storage-path, plus --host/--port conveniences. Starts
the native TCP server on a native engine; when replication or anti-entropy
is enabled in config, the Python control plane (event publisher, sync
manager, TPU Merkle engine) runs alongside in this process.
"""

from __future__ import annotations

import argparse
import signal
import sys
import time
from dataclasses import dataclass
from typing import Any, Optional


@dataclass
class ServingNode:
    """One running node: the native engine and TCP server, the optional
    durable store, and the cluster control plane on top."""

    engine: Any
    server: Any
    node: Any
    storage: Optional[Any] = None
    recovery: Optional[Any] = None

    def close(self) -> None:
        self.node.stop()
        if self.storage is not None:
            # After node.stop() (no more repair/replication writers), before
            # the server/engine teardown: the final drain + shutdown
            # snapshot still read through live handles.
            self.storage.stop()
        self.server.close()
        self.engine.sync()
        self.engine.close()


def start_node(cfg) -> ServingNode:
    """Start a node from a resolved ``Config``: engine, native server,
    durable store when enabled, and the cluster control plane. The CLI
    below and ``chip_smoke.py`` both start nodes through here."""
    from merklekv_tpu.native_bindings import NativeEngine, NativeServer
    from merklekv_tpu.version import __version__

    engine = NativeEngine(cfg.engine, cfg.storage_path)

    # Durable subsystem. The data dir is per-port (node_data_dir) so nodes
    # sharing a cwd-relative storage_path — the multi-node test shape —
    # cannot collide; the directory flock rejects whatever slips past that.
    # On a FIXED port the dir is known up front, so recovery completes
    # before the listening socket even exists — no window where a client
    # reads pre-recovery state or writes an un-journaled key.
    storage = recovery = None
    if cfg.storage.enabled:
        from merklekv_tpu.storage import DurableStore, node_data_dir

        if cfg.port != 0:
            storage = DurableStore(
                engine, cfg.storage, node_data_dir(cfg.storage_path, cfg.port)
            )
            recovery = storage.recover()

    server = NativeServer(
        engine, cfg.host, cfg.port, version=__version__,
        exit_on_shutdown=False, io_threads=cfg.server.io_threads,
        reuseport=cfg.server.reuseport, zero_copy=cfg.server.zero_copy,
        max_line=cfg.server.max_line_bytes,
    )
    if cfg.storage.enabled:
        # BEFORE start(): stage change events from the very first accepted
        # command — writes landing before the drain thread spins up wait in
        # the native queue instead of silently bypassing the WAL.
        server.enable_events(True)
    server.start()

    if cfg.storage.enabled:
        if storage is None:
            # port 0: the dir derives from the just-bound port; recovery
            # still finishes before the readiness line harnesses gate on.
            storage = DurableStore(
                engine, cfg.storage, node_data_dir(cfg.storage_path, server.port)
            )
            recovery = storage.recover()
        storage.start()

    # Always wire the cluster control plane: the SYNC command must work on a
    # bare node (reference parity — SyncManager is unconditional,
    # server.rs:388-390); replication/anti-entropy loops only start when
    # enabled in config.
    from merklekv_tpu.cluster.node import ClusterNode

    node = ClusterNode(cfg, engine, server, storage=storage)
    node.start()
    return ServingNode(engine, server, node, storage, recovery)


def main(argv: list[str] | None = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "walcheck":
        # Offline tool: verify WAL frames + snapshot root stamps without a
        # server (docs/PERSISTENCE.md "Verification").
        from merklekv_tpu.storage.walcheck import main as walcheck_main

        return walcheck_main(argv[1:])
    if argv and argv[0] == "top":
        # Live cluster dashboard: polls STATS/METRICS/PEERS over a node
        # list and renders rates (docs/OBSERVABILITY.md "top").
        from merklekv_tpu.obs.top import main as top_main

        return top_main(argv[1:])
    if argv and argv[0] == "blackbox":
        # Offline post-mortem: merge flight-recorder spills from one or
        # more nodes into an ordered cluster timeline + anomaly report
        # (docs/OBSERVABILITY.md "Post-mortem forensics").
        from merklekv_tpu.obs.blackbox import main as blackbox_main

        return blackbox_main(argv[1:])
    if argv and argv[0] == "router":
        # Request plane: one address dumb clients can point at in a
        # partitioned cluster — pooled epoll io workers, pipelined
        # per-partition fan-out, optional lease-guarded read cache
        # (docs/PROTOCOL.md "Router semantics"); smart clients route
        # themselves and skip this hop. --legacy-threads runs the old
        # thread-per-connection thin router (the measured A/B baseline).
        from merklekv_tpu.requestplane.router import main as router_main

        return router_main(argv[1:])
    if argv and argv[0] == "rebalance":
        # Live partition rebalancing: drive an online split (epoch E+1)
        # against the serving cluster with zero-loss handoff
        # (docs/DEPLOYMENT.md "Online rebalancing").
        from merklekv_tpu.cluster.rebalance import main as rebalance_main

        return rebalance_main(argv[1:])
    if argv and argv[0] == "trace":
        # Cross-node causal-trace assembly: TRACEDUMP from every node,
        # stitched into one Perfetto-loadable Chrome trace
        # (docs/OBSERVABILITY.md "Causal tracing").
        from merklekv_tpu.obs.tracewire import main as trace_main

        return trace_main(argv[1:])

    p = argparse.ArgumentParser(prog="merklekv_tpu")
    p.add_argument("--config", help="TOML config file")
    p.add_argument("--engine", help="storage engine: mem|rwlock|kv|log|sled")
    p.add_argument("--storage-path", help="data dir for durable storage")
    p.add_argument("--host")
    p.add_argument("--port", type=int)
    p.add_argument(
        "--durable",
        action="store_true",
        help="enable the [storage] WAL+snapshot subsystem",
    )
    p.add_argument(
        "--metrics-port",
        type=int,
        help="serve Prometheus /metrics (+/healthz) on this HTTP port "
             "(-1: ephemeral; overrides [observability] http_port)",
    )
    args = p.parse_args(argv)

    from merklekv_tpu.config import load_or_default

    # Join the multi-host jax cluster BEFORE any device touch when
    # MKV_COORDINATOR is set — the device data plane then runs over the
    # global mesh (docs/DEPLOYMENT.md "Multi-host"). Gated on the env var so
    # a bare node never pays the jax import at startup.
    import os

    if os.environ.get("MKV_COORDINATOR"):
        from merklekv_tpu.parallel import multihost

        multihost.initialize()

    cfg = load_or_default(args.config)
    if args.engine:
        cfg.engine = args.engine
    if args.storage_path:
        cfg.storage_path = args.storage_path
    if args.host:
        cfg.host = args.host
    if args.port is not None:
        cfg.port = args.port
    if args.durable:
        cfg.storage.enabled = True
    if args.metrics_port is not None:
        if args.metrics_port < -1:
            # Same rule the [observability] config-file path enforces.
            p.error(f"--metrics-port must be -1 (ephemeral), 0 (disabled), "
                    f"or a TCP port, got {args.metrics_port}")
        cfg.observability.http_port = args.metrics_port

    served = start_node(cfg)
    node, server, storage = served.node, served.server, served.storage

    # Readiness line LAST: spawning harnesses treat it as "fully up",
    # including the replication subscription (QoS-0 — a publish before the
    # peer subscribes is lost until anti-entropy repairs it).
    print(
        f"merklekv_tpu listening on {cfg.host}:{server.port} "
        f"(engine={cfg.engine})",
        flush=True,
    )
    if storage is not None:
        # After the readiness line — spawning harnesses parse line 1 only.
        print(f"storage: recovered {served.recovery.summary()}", flush=True)
    if node.metrics_port is not None:
        # After the readiness line, same rule; CI's exporter smoke job and
        # ops harnesses parse this to find an ephemeral metrics port.
        print(f"metrics: http://{cfg.observability.http_host}:"
              f"{node.metrics_port}/metrics", flush=True)

    stop = {"flag": False}

    def on_signal(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)

    try:
        while not stop["flag"] and not server.stopping:
            time.sleep(0.1)
    finally:
        served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
