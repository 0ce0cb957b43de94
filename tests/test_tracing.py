"""Tracing spans + metrics registry."""

import json
import logging

import pytest

from merklekv_tpu.utils.tracing import Metrics, get_metrics, span


def test_span_emits_json_and_aggregates(caplog):
    m = get_metrics()
    m.reset()
    with caplog.at_level(logging.INFO, logger="merklekv"):
        with span("test.op", peer="p1") as rec:
            rec["items"] = 3
    records = [json.loads(r.message) for r in caplog.records]
    assert records and records[0]["span"] == "test.op"
    assert records[0]["peer"] == "p1"
    assert records[0]["items"] == 3
    assert records[0]["seconds"] >= 0
    snap = m.snapshot()
    assert snap["spans"]["test.op"]["count"] == 1


def test_span_records_errors(caplog):
    get_metrics().reset()
    with caplog.at_level(logging.INFO, logger="merklekv"):
        with pytest.raises(ValueError):
            with span("test.fail"):
                raise ValueError("boom")
    rec = json.loads(caplog.records[0].message)
    assert rec["error"] == "ValueError: boom"


def test_metrics_counters_thread_safe():
    import threading

    m = Metrics()

    def bump():
        for _ in range(1000):
            m.inc("x")

    ts = [threading.Thread(target=bump) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert m.snapshot()["counters"]["x"] == 8000


def test_sync_manager_emits_metrics():
    from merklekv_tpu.cluster.sync import SyncManager
    from merklekv_tpu.native_bindings import NativeEngine, NativeServer

    get_metrics().reset()
    with NativeEngine("mem") as remote_eng:
        remote_eng.set(b"mk", b"mv")
        with NativeServer(remote_eng, "127.0.0.1", 0) as srv:
            srv.start()
            with NativeEngine("mem") as local_eng:
                SyncManager(local_eng, device="cpu").sync_once(
                    "127.0.0.1", srv.port
                )
    snap = get_metrics().snapshot()
    assert snap["counters"]["anti_entropy.syncs"] == 1
    assert snap["counters"]["anti_entropy.keys_repaired"] == 1
    assert snap["spans"]["anti_entropy.sync_once"]["count"] == 1


def test_span_lands_on_profiler_host_plane(tmp_path):
    """A span is a profiler host annotation: inside a trace taken without
    the Python tracer it shows on ``/host:CPU`` by name, from any thread."""
    import glob
    import threading

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with span("test.traced.main", log=False):
            pass
        def other():
            with span("test.traced.thread", log=False):
                pass

        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for ev in line.events
    }
    assert {"test.traced.main", "test.traced.thread"} <= names


def test_quiet_logger_span_builds_no_record(caplog, monkeypatch):
    """Above INFO (or with log=False) a span formats nothing — no JSON
    record, no json.dumps — yet still aggregates into the registry."""
    from merklekv_tpu.utils import tracing

    def no_dumps(*a, **k):
        raise AssertionError("json.dumps called for a span nobody logs")

    monkeypatch.setattr(tracing.json, "dumps", no_dumps)
    m = get_metrics()
    m.reset()
    with caplog.at_level(logging.WARNING, logger="merklekv"):
        with span("test.quiet", peer="p1") as rec:
            rec["items"] = 3
    with caplog.at_level(logging.INFO, logger="merklekv"):
        with span("test.hot", log=False):
            pass
    assert not caplog.records
    spans = m.snapshot()["spans"]
    assert spans["test.quiet"]["count"] == 1
    assert spans["test.hot"]["count"] == 1
    assert m.snapshot()["histograms"]["span.test.hot"]["count"] == 1


def _lowered_programs():
    """(name, lower) for every named device program, at tiny shapes."""
    import jax
    import jax.numpy as jnp

    from merklekv_tpu.merkle import incremental, jax_engine
    from merklekv_tpu.parallel import sharded_merkle as sm
    from merklekv_tpu.parallel.mesh import make_mesh

    u32, i32 = jnp.uint32, jnp.int32

    def sds(*shape, dtype=u32):
        return jax.ShapeDtypeStruct(shape, dtype)

    c = 16
    levels = tuple(sds(c >> j, 8) for j in range(c.bit_length()))
    mesh = make_mesh({"key": 2}, devices=jax.devices()[:2])
    mesh2 = make_mesh({"replica": 2, "key": 2}, devices=jax.devices()[:4])
    n_local = sm._local_level_count(c, 2)
    return {
        "mkv_build_levels": lambda: incremental._build_fn(c, False)
        .lower(sds(c, 8)),
        "mkv_scatter": lambda: incremental._scatter_fn(c, 16, False)
        .lower(levels, sds(16, dtype=i32), sds(16, 8)),
        "mkv_restructure": lambda: incremental._restructure_fn(
            c, c, 16, False).lower(sds(c, 8), sds(c, dtype=i32),
                                   sds(16, dtype=i32), sds(16, 8)),
        "mkv_root_walk": lambda: incremental._ref_root_fn(c, False)
        .lower(levels, sds(dtype=i32)),
        "mkv_level_gather": lambda: incremental.mkv_level_gather
        .lower(sds(c, 8), sds(dtype=i32), 4),
        "mkv_leaf_digests": lambda: jax_engine._leaf_fn(False)
        .lower(sds(4, 1, 16), sds(4, dtype=i32)),
        "mkv_shard_build_levels": lambda: sm.sharded_levels_program(
            mesh, "key", c, False).lower(sds(c, 8)),
        "mkv_shard_scatter": lambda: sm.sharded_scatter_program(
            mesh, "key", c, 16, False).lower(
                *levels[:n_local], sds(2, 16, dtype=i32), sds(2, 16, 8)),
        "mkv_shard_restructure": lambda: sm.sharded_restructure_program(
            mesh, "key", c, c, 16, False).lower(
                sds(c, 8), sds(c, dtype=i32), sds(16, dtype=i32),
                sds(16, 8)),
        "mkv_shard_tree_root": lambda: sm._tree_root_program(
            mesh, "key", False).lower(sds(c, 8)),
        "mkv_shard_divergence": lambda: sm.divergence_program(mesh, "key")
        .lower(sds(3, c, 8), sds(3, c, dtype=jnp.bool_)),
        "mkv_shard_divergence_2d": lambda: sm._divergence_2d_program(
            mesh2, "replica", "key").lower(
                sds(4, c, 8), sds(4, c, dtype=jnp.bool_)),
        "mkv_shard_anti_entropy_step": lambda: sm._anti_entropy_program(
            mesh, "key", False).lower(
                sds(c, 1, 16), sds(c, dtype=i32), sds(3, c, 8),
                sds(3, c, dtype=jnp.bool_)),
    }


PROGRAMS = [
    "mkv_build_levels", "mkv_scatter", "mkv_restructure", "mkv_root_walk",
    "mkv_level_gather", "mkv_leaf_digests", "mkv_shard_build_levels",
    "mkv_shard_scatter", "mkv_shard_restructure", "mkv_shard_tree_root",
    "mkv_shard_divergence", "mkv_shard_divergence_2d",
    "mkv_shard_anti_entropy_step",
]


@pytest.mark.parametrize("name", PROGRAMS)
def test_device_program_carries_its_name(name):
    """Each device program lowers under its ``mkv_*`` name, so a profiler
    trace's module and scope strings say which program an op belongs to."""
    text = _lowered_programs()[name]().as_text()
    assert f"@jit_{name}" in text
