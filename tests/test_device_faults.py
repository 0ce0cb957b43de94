"""Device-plane fault containment (ISSUE 13): unit layer.

The dispatch guard (deadline / classify / retry-once / abandonment), the
shared environment|code classifier, the chaos injector, the degradation
ladder's policy, the CPU golden rung's bit-identity, the guarded diff
fallback, bench_gate's structured-weather skip, and the blackbox anomaly
surfacing. The mirror-level chaos (per-rung transitions on the 8-way host
mesh, pump-alive under hang, scrub, heal) lives in test_device_ladder.py.
"""

import threading
import time

import pytest

from merklekv_tpu.cluster.retry import RetryPolicy
from merklekv_tpu.device.guard import (
    DeviceDispatchError,
    DispatchGuard,
    DispatchHungError,
)
from merklekv_tpu.device.ladder import DeviceBackendLadder, rung_sequence
from merklekv_tpu.testing.device_faults import DeviceFaultInjector
from merklekv_tpu.utils.errorkind import (
    CODE,
    ENVIRONMENT,
    classify_error,
    classify_exception,
)

FAST = RetryPolicy(first_delay=0.01, max_delay=0.02, jitter=0.0, attempts=2)


# ------------------------------------------------------------ classifier

@pytest.mark.parametrize("msg", [
    "RuntimeError: need 8 devices, have 1",
    "unable to initialize backend 'tpu'",
    "DEADLINE_EXCEEDED: rpc timed out",
    "watchdog: 240s deadline expired in phase 'mesh-init'",
    "device dispatch 'shard8_build' failed: dispatch deadline 500ms "
    "expired",
    "connection reset by peer",
])
def test_classifier_environment_patterns(msg):
    assert classify_error(msg) == ENVIRONMENT


@pytest.mark.parametrize("msg", [
    "AssertionError: sharded root != single-device root",
    "ValueError: shapes (8, 8) and (4, 8) are incompatible",
    "KeyError: b'missing'",
])
def test_classifier_code_default(msg):
    assert classify_error(msg) == CODE


def test_classifier_exception_types_are_environment():
    # OSError-family failures are environment even with pattern-less
    # messages (errno text varies by libc).
    assert classify_exception(OSError("whatever")) == ENVIRONMENT
    assert classify_exception(TimeoutError()) == ENVIRONMENT
    assert classify_exception(ValueError("bad shape")) == CODE


def test_classifier_is_the_probes_classifier():
    """__graft_entry__ must classify through the shared module (the
    dedup satellite: one pattern table, three consumers)."""
    import __graft_entry__ as ge

    assert ge._classify_error is classify_error


# ------------------------------------------------------------ guard

def test_guard_passthrough_and_deadline_abandonment():
    g = DispatchGuard(deadline_ms=300, policy=FAST)
    assert g.run("t", lambda: 41 + 1) == 42
    t0 = time.monotonic()
    with pytest.raises(DispatchHungError) as ei:
        g.run("t", lambda: time.sleep(3))
    assert time.monotonic() - t0 < 2.0, "guard waited past its deadline"
    assert ei.value.kind == ENVIRONMENT
    # The wedged worker was abandoned; a fresh one serves the next call.
    assert g.run("t", lambda: 7) == 7


def test_guard_retries_environment_once_then_raises_typed():
    g = DispatchGuard(deadline_ms=0, policy=FAST)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("unable to initialize backend (blip)")
        return "ok"

    assert g.run("t", flaky) == "ok"
    assert calls["n"] == 2  # one transparent retry

    calls["n"] = 0

    def dead():
        calls["n"] += 1
        raise RuntimeError("unable to initialize backend (persistent)")

    with pytest.raises(DeviceDispatchError) as ei:
        g.run("t", dead)
    assert calls["n"] == 2  # retried once, then typed raise
    assert ei.value.kind == ENVIRONMENT
    assert ei.value.label == "t"


def test_guard_code_errors_never_retry():
    g = DispatchGuard(deadline_ms=0, policy=FAST)
    calls = {"n": 0}

    def buggy():
        calls["n"] += 1
        raise ValueError("scatter index shape mismatch")

    with pytest.raises(DeviceDispatchError) as ei:
        g.run("t", buggy)
    assert calls["n"] == 1
    assert ei.value.kind == CODE


def test_guard_nested_call_runs_inline_no_false_hang():
    """A guarded call issued FROM the guard worker (query-path gather
    triggering a staged flush) must run inline, not deadlock into a
    false hang against the busy single worker."""
    from merklekv_tpu.obs.metrics import get_metrics

    g = DispatchGuard(deadline_ms=500, policy=FAST)
    before = get_metrics().snapshot()["counters"].get("device.dispatches", 0)
    assert g.run("outer", lambda: g.run("inner", lambda: 5)) == 5
    # The inner call is part of the outer dispatch: counted once.
    after = get_metrics().snapshot()["counters"]["device.dispatches"]
    assert after - before == 1


def test_guard_first_compile_is_not_a_hang():
    """A program whose cold compile outlasts the deadline: ``call``
    compiles it ahead of the guarded dispatch and succeeds, and the
    executable is reused per signature; ``run`` around the same cold
    compile is abandoned as a hang."""
    import jax
    import numpy as np

    from merklekv_tpu.ops.sha256 import sha256_blocks

    from merklekv_tpu.obs.metrics import get_metrics

    def compiles() -> int:
        spans = get_metrics().snapshot()["spans"]
        return spans.get("device.compile", {}).get("count", 0)

    # CPU: ~0.4 s to compile, ~1 ms to run.
    g = DispatchGuard(deadline_ms=150, policy=FAST)
    blocks = np.zeros((4, 3, 16), np.uint32)
    nb = np.ones(4, np.int32)
    with pytest.raises(DispatchHungError):
        g.run("build", lambda: jax.jit(sha256_blocks)(blocks, nb))
    prog = jax.jit(sha256_blocks)
    c0 = compiles()
    out = np.asarray(g.call("build", prog, blocks, nb))
    np.testing.assert_array_equal(out, np.asarray(sha256_blocks(blocks, nb)))
    assert g.compiled("build", prog, blocks, nb) is g.compiled(
        "build", prog, blocks, nb
    )
    assert compiles() - c0 == 1  # a cache hit records no compile span
    with pytest.raises(DeviceDispatchError):
        g.compiled("build", prog, blocks, nb[:3])  # shapes do not trace


# ------------------------------------------------------------ injector

def test_injector_fail_nth_and_count():
    inj = DeviceFaultInjector(match="scatter", at=2, count=1)
    g = DispatchGuard(deadline_ms=0, policy=FAST)
    inj.install()
    try:
        assert g.run("scatter", lambda: 1) == 1   # matched #1: below at
        # matched #2 fails, matched #3 (the guard's retry) passes — the
        # injected blip is absorbed by the retry budget.
        assert g.run("scatter", lambda: 2) == 2
        assert inj.failures == 1
        assert g.run("build", lambda: 3) == 3     # label not matched
    finally:
        inj.uninstall()


def test_injector_persistent_until_heal():
    inj = DeviceFaultInjector(match="shard*", mode="fail")
    g = DispatchGuard(deadline_ms=0, policy=FAST)
    with inj:
        with pytest.raises(DeviceDispatchError):
            g.run("shard8_build", lambda: 1)
        with pytest.raises(DeviceDispatchError):
            g.run("shard2_scatter", lambda: 1)
        assert g.run("build", lambda: 1) == 1  # single-device unscathed
        inj.heal()
        assert g.run("shard8_build", lambda: 1) == 1
        inj.unheal()
        with pytest.raises(DeviceDispatchError):
            g.run("shard8_build", lambda: 1)


def test_injector_hang_exercises_abandonment():
    inj = DeviceFaultInjector(match="*", mode="hang", hang_s=2.0)
    g = DispatchGuard(deadline_ms=200, policy=FAST)
    with inj:
        t0 = time.monotonic()
        with pytest.raises(DispatchHungError):
            g.run("build", lambda: 1)
        assert time.monotonic() - t0 < 1.5
        assert inj.hangs == 1
    assert g.run("build", lambda: 1) == 1  # uninstalled + fresh worker


def test_injector_env_spec_roundtrip():
    inj = DeviceFaultInjector.from_spec("fail:shard*:3")
    assert inj._match == "shard*" and inj._mode == "fail" and inj._at == 3
    with pytest.raises(ValueError):
        DeviceFaultInjector.from_spec("fail")
    with pytest.raises(ValueError):
        DeviceFaultInjector(mode="explode")


# ------------------------------------------------------------ ladder policy

def test_rung_sequence_shapes():
    assert rung_sequence(8) == [8, 4, 2, 1, 0]
    assert rung_sequence(2) == [2, 1, 0]
    assert rung_sequence(1) == [1, 0]
    assert rung_sequence(0) == [1, 0]


def test_ladder_degrade_threshold_and_immediate():
    lad = DeviceBackendLadder(8, degrade_after=2, heal_policy=FAST)
    assert lad.current() == 8 and not lad.degraded()
    assert not lad.note_failure(ENVIRONMENT, "drain")
    assert lad.note_failure(ENVIRONMENT, "drain")   # second one steps
    assert lad.current() == 4 and lad.degraded()
    # Success resets the consecutive counter.
    assert not lad.note_failure(ENVIRONMENT, "drain")
    lad.note_success()
    assert not lad.note_failure(ENVIRONMENT, "drain")
    # Build failures step immediately.
    assert lad.note_failure(ENVIRONMENT, "build", immediate=True)
    assert lad.current() == 2
    # Walk to the bottom: the CPU rung never steps further.
    assert lad.note_failure(ENVIRONMENT, "build", immediate=True)
    assert lad.note_failure(ENVIRONMENT, "build", immediate=True)
    assert lad.current() == 0 and lad.at_bottom()
    assert not lad.note_failure(ENVIRONMENT, "build", immediate=True)
    assert lad.current() == 0


def test_ladder_heal_probe_targets_top_first_then_walks_down():
    lad = DeviceBackendLadder(8, degrade_after=1, heal_policy=FAST)
    for _ in range(3):  # 8 -> 4 -> 2 -> 1
        lad.note_failure(ENVIRONMENT, "drain")
    assert lad.current() == 1
    time.sleep(0.03)
    assert lad.heal_due()
    assert lad.probe_target() == 8          # top first: common full heal
    assert lad.note_probe(False) is None
    time.sleep(0.03)
    assert lad.probe_target() == 4          # walks down after a miss
    assert lad.note_probe(False) is None
    time.sleep(0.03)
    assert lad.probe_target() == 2
    assert lad.note_probe(True) == 2        # partial heal climbs there
    assert lad.current() == 2 and lad.degraded()
    time.sleep(0.03)
    assert lad.probe_target() == 8          # keeps probing upward
    assert lad.note_probe(True) == 8
    assert not lad.degraded()


def test_ladder_records_flight_events_and_counters():
    from merklekv_tpu.obs.flightrec import get_recorder

    lad = DeviceBackendLadder(2, degrade_after=1, heal_policy=FAST)
    lad.note_failure(ENVIRONMENT, "drain")
    time.sleep(0.03)
    assert lad.note_probe(True) == 2
    kinds = [e.kind for e in get_recorder().last(10)]
    assert "device_degraded" in kinds and "device_healed" in kinds
    deg = [e for e in get_recorder().last(10)
           if e.kind == "device_degraded"][-1]
    assert deg.fields["from_rung"] == 2 and deg.fields["to_rung"] == 1
    assert deg.fields["kind"] == ENVIRONMENT


# ------------------------------------------------------------ CPU rung

def test_cpu_state_bit_identical_to_golden_tree():
    from merklekv_tpu.merkle.cpu import build_levels
    from merklekv_tpu.merkle.cpu_state import CpuMerkleState
    from merklekv_tpu.merkle.encoding import leaf_hash

    items = {b"cpu:%04d" % i: b"v%d" % i for i in range(111)}
    st = CpuMerkleState.from_items(items.items())

    def golden():
        return build_levels(
            [leaf_hash(k, v) for k, v in sorted(items.items())]
        )

    assert st.root_hex() == golden()[-1][0].hex()
    # Staging contract: pending stays invisible until flush.
    st.apply([(b"cpu:0000", b"changed")])
    assert st.pending_count() == 1
    assert st.root_hex(flush=False) == golden()[-1][0].hex()
    items[b"cpu:0000"] = b"changed"
    st.flush_pending()
    assert st.pending_count() == 0
    assert st.root_hex(flush=False) == golden()[-1][0].hex()
    # Structural change + every-level TREELEVEL parity.
    st.apply([(b"zzz:new", b"n"), (b"cpu:0001", None)])
    items[b"zzz:new"] = b"n"
    del items[b"cpu:0001"]
    st.flush_pending()
    glv = golden()
    for lvl in range(len(glv)):
        rows, n = st.level_nodes(lvl, 0, len(glv[lvl]))
        assert n == len(items)
        assert [d for _, d in rows] == glv[lvl]
    assert st._n_shards == 0  # the backend_level code for the CPU rung


# ------------------------------------------------------------ diff fallback

def test_divergence_engine_falls_back_bit_identical_under_fault():
    import numpy as np

    from merklekv_tpu.merkle.diff import (
        divergence_masks_engine,
        divergence_masks_np,
    )

    rng = np.random.RandomState(3)
    n, r = 64, 4
    digests = np.tile(
        rng.randint(0, 2**32, size=(1, n, 8), dtype=np.uint64).astype(
            np.uint32
        ),
        (r, 1, 1),
    )
    digests[2, 5] ^= 1
    present = np.ones((r, n), bool)
    present[3, 0] = False
    golden = divergence_masks_np(digests, present)
    with DeviceFaultInjector(match="shard*_diff", mode="fail"):
        masks = divergence_masks_engine(digests, present, min_keys=0)
    assert np.array_equal(np.asarray(masks), golden)


# ------------------------------------------------------------ bench_gate

def test_bench_gate_skips_environment_weather_rounds():
    import sys
    sys.path.insert(0, "tools")
    from bench_gate import extract_scenarios, round_weather

    weather = {
        "rc": 0,
        "parsed": {
            "metric": "merkle_rebuild_diff_keys_per_s",
            "value": None,
            "unit": "keys/s",
            "error": "RuntimeError: unable to initialize backend",
            "error_kind": "environment",
        },
        "tail": "",
    }
    assert extract_scenarios(weather) == {}  # never a baseline
    assert round_weather(weather) == "environment"
    # A code-kind crash is also skipped but not called weather.
    broken = {
        "rc": 1,
        "parsed": {
            "metric": "m", "value": None, "unit": "",
            "error": "AssertionError: boom", "error_kind": "code",
        },
    }
    assert round_weather(broken) == "code"
    # Legacy rounds without the field keep the old anonymous skip.
    assert round_weather({"rc": 1, "parsed": None}) is None


def test_bench_gate_direction_for_fault_recovery_metrics():
    sysmod = __import__("sys")
    sysmod.path.insert(0, "tools")
    from bench_gate import lower_is_better

    assert not lower_is_better("device_fault_queries_per_s", "queries/s")
    assert lower_is_better("device_fault_reclimb_ms", "ms")


# ------------------------------------------------------------ blackbox

def test_blackbox_surfaces_device_ladder_events_as_anomalies():
    from merklekv_tpu.obs.blackbox import SpillDoc, find_anomalies, merge_timeline
    from merklekv_tpu.obs.flightrec import FlightEvent

    def evt(evt_kind, seq, **fields):
        # The wire gotcha all over again: an event's own `kind` field
        # (the classifier verdict) must not collide with the FlightEvent
        # kind — keyword-splatting both through one signature does.
        return FlightEvent(
            seq=seq, wall_ns=1_000_000_000 + seq, mono_ns=seq,
            kind=evt_kind,
            fields={k: str(v) for k, v in fields.items()},
        )

    doc = SpillDoc(
        path="x/flight", meta={"node": "n1"},
        events=[
            evt("device_degraded", 1, from_rung=8, to_rung=4,
                kind="environment", where="drain"),
            evt("device_fallback", 2, rung=4),
            evt("device_corruption", 3, leaf_index=17, rung=4),
            evt("device_healed", 4, from_rung=4, to_rung=8),
        ],
    )
    timeline = merge_timeline([doc])
    anomalies = find_anomalies([doc], timeline)
    kinds = {a.kind for a in anomalies}
    assert "device_degraded" in kinds
    assert "device_fallback" in kinds
    assert "device_corruption" in kinds
    deg = [a for a in anomalies if a.kind == "device_degraded"][0]
    assert "environment" in deg.detail and "8 -> 4" in deg.detail


# ------------------------------------------------------------ guard metrics

def test_guard_counts_timeouts_and_retries():
    from merklekv_tpu.obs.metrics import get_metrics

    def counter(name):
        return get_metrics().snapshot()["counters"].get(name, 0)

    base_t = counter("device.guard_timeouts")
    base_r = counter("device.guard_retries")
    g = DispatchGuard(deadline_ms=150, policy=FAST)
    with pytest.raises(DispatchHungError):
        g.run("t", lambda: time.sleep(1.5))
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("connection reset by peer")
        return 1

    assert g.run("t", flaky) == 1
    assert counter("device.guard_timeouts") == base_t + 1
    assert counter("device.guard_retries") == base_r + 1
