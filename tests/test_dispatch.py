"""Backend dispatch: production tree paths must route through the Pallas
kernels on TPU and stay bit-identical to the scan formulation.

Round-4 VERDICT item 3: the Pallas kernels only served the bench; now
merkle/incremental.py and parallel/sharded_merkle.py route hashing through
ops/dispatch.py. On the CPU mesh the WIRING is pinned by spying on the
dispatch (full interpretation of the unrolled kernels is intractable off
TPU — see tests/test_sha256_pallas.py); the parity tests themselves run
compiled on a real chip.
"""

import numpy as np
import pytest

import jax

from merklekv_tpu.merkle.cpu import build_levels
from merklekv_tpu.merkle.encoding import leaf_hash
from merklekv_tpu.ops import dispatch

on_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="compiled pallas kernels need TPU"
)


def _golden_root(items: dict[bytes, bytes]) -> str:
    if not items:
        return "0" * 64
    hashes = [leaf_hash(k, v) for k, v in sorted(items.items())]
    return build_levels(hashes)[-1][0].hex()


def test_dispatch_mode_selection(monkeypatch):
    monkeypatch.setenv("MKV_SHA256_BACKEND", "scan")
    assert not dispatch.use_pallas()
    monkeypatch.setenv("MKV_SHA256_BACKEND", "pallas")
    assert dispatch.use_pallas()
    monkeypatch.delenv("MKV_SHA256_BACKEND")
    assert dispatch.use_pallas() == (jax.default_backend() == "tpu")


def test_production_paths_route_through_dispatch(monkeypatch):
    """With the backend forced to "pallas", the incremental tree's build,
    scatter, restructure and root-walk programs must reach the Pallas entry
    points.
    The spy delegates to the scan math so the roots stay correct on CPU."""
    import merklekv_tpu.ops.sha256_pallas as sp
    from merklekv_tpu.merkle import incremental, jax_engine
    from merklekv_tpu.ops.sha256 import sha256_blocks, sha256_node_pairs

    calls = {"leaf": 0, "node": 0}

    def spy_leaf(blocks, nblocks, interpret=None):
        calls["leaf"] += 1
        return sha256_blocks(blocks, nblocks)

    def spy_node(left, right, interpret=None):
        calls["node"] += 1
        return sha256_node_pairs(left, right)

    def spy_level(cur, interpret=None):
        # The full build reduces levels via the adjacent-pair level kernel
        # (hash_node_level); scatter/restructure still gather explicit
        # left/right pairs through node_pairs_pallas.
        calls["node"] += 1
        p = cur.shape[0] // 2
        return sha256_node_pairs(cur[0 : 2 * p : 2], cur[1 : 2 * p : 2])

    monkeypatch.setattr(sp, "leaf_digests_pallas", spy_leaf)
    monkeypatch.setattr(sp, "node_pairs_pallas", spy_node)
    monkeypatch.setattr(sp, "node_level_pallas", spy_level)
    # Interp narrow-level fallback would bypass the node spy on CPU.
    monkeypatch.setattr(sp, "_MIN_PALLAS_PAIRS_INTERP", 0)
    monkeypatch.setenv("MKV_SHA256_BACKEND", "pallas")
    # Fresh compiled-program cache entries for the forced backend: the
    # factories key on use_pallas(), so these traces re-read the dispatch.
    incremental._build_fn.cache_clear()
    incremental._scatter_fn.cache_clear()
    incremental._restructure_fn.cache_clear()
    incremental._ref_root_fn.cache_clear()
    jax_engine._leaf_fn.cache_clear()

    items = {b"rk%03d" % i: b"rv%d" % i for i in range(21)}
    st = incremental.DeviceMerkleState.from_items(items.items())
    assert st.root_hex() == _golden_root(items)
    assert calls["leaf"] >= 1  # initial leaf hashing went through Pallas
    assert calls["node"] >= 1  # tree reduction went through Pallas

    # Scatter path.
    calls["node"] = 0
    items[b"rk000"] = b"changed"
    st.apply([(b"rk000", b"changed")])
    assert st.root_hex() == _golden_root(items)
    assert calls["node"] >= 1

    # Restructure path.
    calls["leaf"] = calls["node"] = 0
    items[b"rk999"] = b"inserted"
    st.apply([(b"rk999", b"inserted")])
    assert st.root_hex() == _golden_root(items)
    assert calls["node"] >= 1

    # Cleanup: drop the spy-traced programs so later tests re-trace real ones.
    incremental._build_fn.cache_clear()
    incremental._scatter_fn.cache_clear()
    incremental._restructure_fn.cache_clear()
    incremental._ref_root_fn.cache_clear()
    jax_engine._leaf_fn.cache_clear()


def test_sharded_step_routes_through_dispatch(monkeypatch):
    """The SPMD step's leaf hashing + local reduction honor the dispatch."""
    import merklekv_tpu.ops.sha256_pallas as sp
    from merklekv_tpu.merkle.jax_engine import leaf_digests, tree_root
    from merklekv_tpu.merkle.packing import pack_leaves
    from merklekv_tpu.ops.sha256 import (
        digest_to_bytes,
        sha256_blocks,
        sha256_node_pairs,
    )
    from merklekv_tpu.parallel import make_mesh
    from merklekv_tpu.parallel.sharded_merkle import sharded_anti_entropy_step

    calls = {"leaf": 0, "node": 0}
    monkeypatch.setattr(
        sp, "leaf_digests_pallas",
        lambda b, nb, interpret=None: (
            calls.__setitem__("leaf", calls["leaf"] + 1),
            sha256_blocks(b, nb),
        )[1],
    )
    monkeypatch.setattr(
        sp, "node_pairs_pallas",
        lambda l, r, interpret=None: (
            calls.__setitem__("node", calls["node"] + 1),
            sha256_node_pairs(l, r),
        )[1],
    )
    monkeypatch.setattr(
        sp, "node_level_pallas",
        lambda cur, interpret=None: (
            calls.__setitem__("node", calls["node"] + 1),
            sha256_node_pairs(
                cur[0 : 2 * (cur.shape[0] // 2) : 2],
                cur[1 : 2 * (cur.shape[0] // 2) : 2],
            ),
        )[1],
    )
    monkeypatch.setattr(sp, "_MIN_PALLAS_PAIRS_INTERP", 0)
    monkeypatch.setenv("MKV_SHA256_BACKEND", "pallas")

    mesh = make_mesh()  # all 8 virtual CPU devices on the "key" axis
    n = 64
    keys = [b"sk%04d" % i for i in range(n)]
    values = [b"sv%d" % i for i in range(n)]
    packed = pack_leaves(keys, values)
    digests = np.stack([np.asarray(leaf_digests(keys, values))] * 2)
    present = np.ones((2, n), bool)
    root, masks, counts = sharded_anti_entropy_step(
        mesh, packed.blocks, packed.nblocks, digests, present
    )
    monkeypatch.setenv("MKV_SHA256_BACKEND", "scan")
    expect = digest_to_bytes(np.asarray(tree_root(leaf_digests(keys, values))))
    assert digest_to_bytes(np.asarray(root)) == expect
    assert int(np.asarray(counts).sum()) == 0
    assert calls["leaf"] >= 1 and calls["node"] >= 1
    # Drop the spy-traced program so later callers re-trace the real one.
    from merklekv_tpu.parallel.sharded_merkle import _anti_entropy_program

    _anti_entropy_program.cache_clear()


# ------------------------------------------------ compiled parity (real TPU)

@on_tpu
def test_incremental_tree_parity_on_tpu():
    """DeviceMerkleState through every mutation path on the real chip (the
    default dispatch picks the compiled Pallas kernels there)."""
    from merklekv_tpu.merkle.incremental import DeviceMerkleState

    assert dispatch.use_pallas()
    items = {b"pk%04d" % i: b"pv%d" % i for i in range(4097)}
    st = DeviceMerkleState.from_items(items.items())
    assert st.root_hex() == _golden_root(items)

    for i in range(7):
        items[b"pk%04d" % i] = b"upd%d" % i
    st.apply([(b"pk%04d" % i, b"upd%d" % i) for i in range(7)])
    assert st.root_hex() == _golden_root(items)
    assert st.incremental_batches >= 1

    items[b"pk9999"] = b"new"
    del items[b"pk0003"]
    st.apply([(b"pk9999", b"new"), (b"pk0003", None)])
    assert st.root_hex() == _golden_root(items)
    assert st.structural_batches >= 1


@on_tpu
def test_sharded_step_parity_on_tpu():
    from merklekv_tpu.merkle.jax_engine import leaf_digests, tree_root
    from merklekv_tpu.merkle.packing import pack_leaves
    from merklekv_tpu.ops.sha256 import digest_to_bytes
    from merklekv_tpu.parallel import make_mesh
    from merklekv_tpu.parallel.sharded_merkle import sharded_anti_entropy_step

    mesh = make_mesh()
    d = mesh.shape["key"]
    n = d * 512
    keys = [b"sk%06d" % i for i in range(n)]
    values = [b"sv%d" % i for i in range(n)]
    packed = pack_leaves(keys, values)
    digests = np.stack([np.asarray(leaf_digests(keys, values))] * 2)
    present = np.ones((2, n), bool)
    root, masks, counts = sharded_anti_entropy_step(
        mesh, packed.blocks, packed.nblocks, digests, present
    )
    expect = digest_to_bytes(np.asarray(tree_root(leaf_digests(keys, values))))
    assert digest_to_bytes(np.asarray(root)) == expect
