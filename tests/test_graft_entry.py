"""Driver entry-point contract tests.

``entry()`` must return a jittable fn + args; ``dryrun_multichip(n)`` must
succeed even when the current process has fewer than n devices (it re-execs
into a subprocess that provisions a virtual n-device CPU mesh — the fix for
round 1's red MULTICHIP gate).
"""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__  # noqa: E402


def test_entry_jits():
    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)


def test_dryrun_multichip_inline():
    # conftest provisions 8 virtual CPU devices, so this runs in-process.
    __graft_entry__.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_subprocess():
    # More devices than this process has -> must delegate to a subprocess
    # that self-provisions the larger virtual mesh.
    n = len(jax.devices()) * 2
    __graft_entry__.dryrun_multichip(n)


def test_multichip_phase_breadcrumbs(tmp_path, monkeypatch, capsys):
    """The probe leaves per-phase breadcrumbs: flushed stderr lines (the
    driver's tail capture names the last phase even on a timeout kill)
    and, with MKV_PHASE_FILE set, an incrementally rewritten JSON sidecar
    with per-phase wall times."""
    import json

    phase_file = tmp_path / "phases.json"
    monkeypatch.setenv("MKV_PHASE_FILE", str(phase_file))
    __graft_entry__.dryrun_multichip(8)
    err = capsys.readouterr().err
    assert "# MULTICHIP PHASE mesh-init" in err
    assert "# MULTICHIP PHASE spmd-jit-run" in err
    assert "# MULTICHIP PHASE done" in err
    doc = json.loads(phase_file.read_text())
    names = [p["phase"] for p in doc["phases"]]
    assert names.index("mesh-init") < names.index("spmd-jit-run")
    assert "serving-tree" in names
    # Every completed phase carries its wall time.
    assert all("seconds" in p for p in doc["phases"])


def test_error_kind_classification():
    """A "need 8 devices, have 1" death must classify as an
    ENVIRONMENT failure — the driver's weather, not a code regression — so
    bench triage and blackbox stop paging on device-complement shortfalls.
    Assertion failures from the probe's own math stay "code"."""
    env = __graft_entry__._classify_error
    assert env("RuntimeError: need 8 devices, have 1") == "environment"
    assert env("mesh needs 8 devices, have 1") == "environment"
    assert env("Unable to initialize backend 'tpu'") == "environment"
    assert env("DEADLINE_EXCEEDED: rpc timed out") == "environment"
    assert env("watchdog: 240s deadline expired in phase 'mesh-init'") == (
        "environment"
    )
    assert env("AssertionError: sharded root != single-device root") == "code"
    assert env("TypeError: unsupported operand") == "code"


def test_device_count_flight_event_precedes_mesh_init(tmp_path, monkeypatch):
    """The probe records the delivered device complement (want/have) as a
    phase breadcrumb BEFORE mesh init — and the enumerate breadcrumb lands
    BEFORE the first backend touch, so a hang inside jax.devices() is
    attributed to enumeration, not its predecessor."""
    import json

    phase_file = tmp_path / "phases.json"
    monkeypatch.setenv("MKV_PHASE_FILE", str(phase_file))
    __graft_entry__.dryrun_multichip(8)
    doc = json.loads(phase_file.read_text())
    by_name = {p["phase"]: p for p in doc["phases"]}
    names = [p["phase"] for p in doc["phases"]]
    assert names.index("device-enumerate") < names.index("device-count")
    assert names.index("device-count") < names.index("mesh-init")
    assert by_name["device-count"]["want"] == 8
    assert by_name["device-count"]["have"] >= 8


def test_watchdog_exits_with_sidecar_and_record(tmp_path):
    """A hung probe must die by the INTERNAL watchdog, not the driver's
    rc=124 kill: exit 3, a partial JSON record on stdout naming the stuck
    phase, and the phase sidecar closed out with a watchdog-timeout entry
    (a run reaped at rc=124 otherwise leaves only a stderr tail)."""
    import json
    import subprocess

    phase_file = tmp_path / "phases.json"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = "\n".join(
        [
            "import os, sys, time",
            "os.environ['MKV_MULTICHIP_DEADLINE_S'] = '1'",
            f"os.environ['MKV_PHASE_FILE'] = {str(phase_file)!r}",
            f"sys.path.insert(0, {root!r})",
            "import __graft_entry__ as g",
            "g._start_watchdog()",
            "g._phase('mesh-init-sim')",
            "time.sleep(60)  # simulated hang: never reaches 'done'",
        ]
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=30,
    )
    assert out.returncode == 3, (out.returncode, out.stderr[-1000:])
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] is False
    assert "mesh-init-sim" in rec["error"]
    # A watchdog timeout is backend weather, not a regression.
    assert rec["error_kind"] == "environment"
    assert any(p["phase"] == "mesh-init-sim" for p in rec["phases"])
    doc = json.loads(phase_file.read_text())
    names = [p["phase"] for p in doc["phases"]]
    assert "watchdog-timeout" in names
    # The stuck phase's elapsed time was closed out by the final rewrite.
    stuck = [p for p in doc["phases"] if p["phase"] == "mesh-init-sim"]
    assert stuck and "seconds" in stuck[0]


def test_watchdog_record_names_the_partition(tmp_path):
    """Partitioned runs (MKV_PARTITION_ID set) stamp the active partition
    on every phase breadcrumb, the watchdog's JSON record, and the
    MULTICHIP_FLIGHT.bin dump — a stuck phase then names WHICH
    partition's mesh wedged, not just which phase (the r05-class blind
    timeout, scoped)."""
    import json
    import subprocess

    phase_file = tmp_path / "phases.json"
    flight_file = tmp_path / "MULTICHIP_FLIGHT.bin"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = "\n".join(
        [
            "import os, sys, time",
            "os.environ['MKV_MULTICHIP_DEADLINE_S'] = '1'",
            "os.environ['MKV_PARTITION_ID'] = '3'",
            f"os.environ['MKV_PHASE_FILE'] = {str(phase_file)!r}",
            f"os.environ['MKV_FLIGHT_FILE'] = {str(flight_file)!r}",
            f"sys.path.insert(0, {root!r})",
            "import __graft_entry__ as g",
            "g._start_watchdog()",
            "g._phase('mesh-init-sim')",
            "time.sleep(60)  # simulated hang",
        ]
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=30,
    )
    assert out.returncode == 3, (out.returncode, out.stderr[-1000:])
    # The stderr breadcrumb names the partition inline.
    assert "# MULTICHIP PHASE mesh-init-sim partition=3" in out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["partition"] == 3
    assert all(p.get("partition") == 3 for p in rec["phases"])
    # The flight dump attributes its events to the partitioned probe.
    from merklekv_tpu.obs.flightrec import read_spill

    doc = read_spill(str(flight_file))
    assert doc.node == "multichip-probe-p3"
    kinds = [e.kind for e in doc.events]
    assert "multichip_phase" in kinds
    assert any(
        e.fields.get("partition") in (3, "3") for e in doc.events
    )
