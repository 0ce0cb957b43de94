"""8-way host-platform mesh coverage for the sharded serving tree.

Unlike the in-process suite (which inherits conftest's virtual mesh), this
module spawns a FRESH interpreter that provisions its own 8-device CPU mesh
via ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the exact
recipe the CI integration job and the multichip probe's subprocess
delegation use — and asserts the sharded build, the per-shard-routed
incremental scatter, and the TREELEVEL answers are bit-identical to the
pure-python CPU golden tree across shard counts {1, 2, 8}, including an
update batch that straddles every shard boundary. One subprocess covers the
whole sweep (the jax import dominates, so per-count processes would triple
the cost for no isolation gain).
"""

import json
import os
import subprocess
import sys

import pytest

# integration: spawns a real interpreter. Keeps the subprocess out of the
# unit CI job; the integration job (and tier-1) run it on every PR.
pytestmark = pytest.mark.integration

_SWEEP = r"""
import json
import jax
import sys
sys.path.insert(0, @@REPO@@)

from merklekv_tpu.merkle.cpu import build_levels
from merklekv_tpu.merkle.encoding import leaf_hash
from merklekv_tpu.parallel.sharded_state import ShardedDeviceMerkleState

assert len(jax.devices()) == 8, jax.devices()


def golden_levels(items):
    return build_levels([leaf_hash(k, v) for k, v in sorted(items.items())])


def check_levels(st, items, what):
    glv = golden_levels(items)
    assert st.root_hex() == glv[-1][0].hex(), what
    for lvl in range(len(glv)):
        rows, n = st.level_nodes(lvl, 0, len(glv[lvl]))
        assert n == len(items), (what, lvl)
        assert [d for _, d in rows] == glv[lvl], (what, "level", lvl)


for shards in (1, 2, 8):
    items = {b"mk%05d" % i: b"v%d" % i for i in range(141)}
    st = ShardedDeviceMerkleState.from_items(items.items(), shards=shards)
    check_levels(st, items, (shards, "build"))

    # Scatter batch straddling EVERY shard boundary (last leaf of shard b,
    # first leaf of shard b+1) plus both keyspace extremes.
    skeys = sorted(items)
    l = st._capacity // shards
    batch = {skeys[0]: b"first", skeys[-1]: b"last"}
    for b in range(1, shards):
        for p in (b * l - 1, b * l):
            if p < len(skeys):
                batch[skeys[p]] = b"x%d" % p
    items.update(batch)
    st.apply(list(batch.items()))
    st.flush_pending()
    assert st.incremental_batches >= 1, shards
    check_levels(st, items, (shards, "scatter"))

    # Structural batch (inserts shift leaves ACROSS shard boundaries).
    changes = []
    for i in range(400, 470):
        items[b"aa%05d" % i] = b"n%d" % i
        changes.append((b"aa%05d" % i, b"n%d" % i))
    del items[b"mk00007"]
    changes.append((b"mk00007", None))
    st.apply(changes)
    check_levels(st, items, (shards, "restructure"))

    if shards > 1:
        assert not st._levels[0].sharding.is_fully_replicated, shards

print(json.dumps({"ok": True, "shard_counts": [1, 2, 8]}))
"""


def test_eight_way_host_mesh_parity(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "mesh_sweep.py"
    script.write_text(_SWEEP.replace("@@REPO@@", repr(repo)))
    env = {
        k: v
        for k, v in os.environ.items()
        if k != "XLA_FLAGS"
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
    )
    assert out.returncode == 0, f"sweep failed:\n{out.stdout}\n{out.stderr}"
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] is True and rec["shard_counts"] == [1, 2, 8]
