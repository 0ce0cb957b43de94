"""The structural (insert/delete) path of the device tree against the golden
CPU tree, on seeded random keys at small sizes: the host plan of a
structural batch, restructures that cross a power-of-two capacity both
ways, update-only batches on the in-place path, and the restructure
programs made ready ahead of the first insert."""

import numpy as np
import pytest

from merklekv_tpu.merkle.cpu import build_levels
from merklekv_tpu.merkle.encoding import leaf_hash
from merklekv_tpu.merkle.incremental import DeviceMerkleState
from merklekv_tpu.obs.metrics import get_metrics


def golden_root(items: dict[bytes, bytes]):
    if not items:
        return None
    return build_levels([leaf_hash(k, items[k]) for k in sorted(items)])[-1][0]


def random_keys(rng: np.random.Generator, count: int) -> list[bytes]:
    """Distinct keys of 1-12 random bytes, NUL bytes (trailing ones too)
    among them."""
    out: set[bytes] = set()
    while len(out) < count:
        n = int(rng.integers(1, 13))
        out.add(bytes(rng.choice([0, 1, 65, 97, 255], n).tolist()))
    return sorted(out)


# --------------------------------------------------------- the host plan

@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_plan_restructure_gathers_survivors_into_their_new_slots(seed):
    """The host plan of a mixed batch: the new key array is the sorted
    survivors and inserts, its index maps each key to its slot, and each
    slot gathers its survivor's old slot (-1 for an insert)."""
    rng = np.random.default_rng(seed)
    keys = random_keys(rng, 300)
    held = keys[::2]
    st = DeviceMerkleState.from_items((k, b"v") for k in held)
    # Drawn by index: a NumPy bytes array would drop trailing NULs.
    deletes = sorted(held[i] for i in rng.choice(len(held), 20, replace=False))
    absent = keys[1::2]
    inserts = sorted(absent[i]
                     for i in rng.choice(len(absent), 30, replace=False))
    new_keys, new_index, gather = st._plan_restructure(deletes, inserts)
    want = sorted(set(held) - set(deletes) | set(inserts))
    assert list(new_keys) == want
    assert new_index == {k: i for i, k in enumerate(want)}
    old_slot = {k: i for i, k in enumerate(held)}
    assert gather.tolist() == [old_slot.get(k, -1) for k in want]
    # Planning changes nothing until the restructure lands.
    assert list(st._keys) == held


# ------------------------------------------------------- the device state

def _random_batch(rng, items: dict, universe: list[bytes], size: int,
                  p_delete: float, tag: int):
    batch = []
    for _ in range(size):
        k = universe[int(rng.integers(len(universe)))]
        if rng.random() < p_delete:
            items.pop(k, None)
            batch.append((k, None))
        else:
            v = b"v%d-%d" % (tag, int(rng.integers(1000)))
            items[k] = v
            batch.append((k, v))
    return batch


@pytest.mark.parametrize("seed,n0", [(11, 30), (12, 33), (13, 64), (14, 7)])
def test_interleaved_inserts_updates_deletes_cross_capacity(seed, n0):
    """Mixed batches grow the keyspace past one power of two and shrink it
    back below it; after each one the root, the leaf positions and the
    leaf count equal the golden tree's over the evolved keyspace."""
    rng = np.random.default_rng(seed)
    universe = random_keys(rng, 4 * n0 + 40)
    items = {k: b"init" for k in universe[:n0]}
    st = DeviceMerkleState.from_items(items.items())
    cap0 = st._capacity
    # Grow (mostly inserts) past the capacity, then shrink (mostly
    # deletes) back under it.
    for p_delete, done in ((0.05, lambda: st._capacity > cap0),
                           (0.9, lambda: st._capacity < cap0)):
        for round_ in range(40):
            size = int(rng.integers(1, n0 // 2 + 3))
            st.apply(_random_batch(rng, items, universe, size, p_delete,
                                   round_))
            assert st.root_hash() == golden_root(items), round_
            assert list(st._keys) == sorted(items)
            if items:
                assert st._positions(sorted(items)).tolist() == list(
                    range(len(items)))
            if done():
                break
        assert done()
    assert st.full_rebuilds <= 2  # only an emptied tree builds again


def test_update_only_batch_stays_in_place():
    rng = np.random.default_rng(21)
    keys = random_keys(rng, 90)
    items = {k: b"a" for k in keys}
    st = DeviceMerkleState.from_items(items.items())
    st.root_hash()
    seen = []
    orig = DeviceMerkleState._update_in_place

    def spy(self, batch):
        seen.append(self._positions([k for k, _ in batch]).tolist())
        return orig(self, batch)

    DeviceMerkleState._update_in_place = spy
    try:
        for round_ in range(3):
            pick = sorted(rng.choice(len(keys), 17, replace=False).tolist())
            batch = [(keys[i], b"u%d" % round_) for i in pick]
            # A delete of an absent key changes nothing structural.
            batch.append((b"\xff" * 20, None))
            for k, v in batch[:-1]:
                items[k] = v
            st.apply(batch)
            assert st.root_hash() == golden_root(items)
            assert seen[-1] == pick  # positions in sorted order
    finally:
        DeviceMerkleState._update_in_place = orig
    assert st.structural_batches == 0
    assert st.incremental_batches == 3


def test_structural_batches_count_and_key_index_span():
    m = get_metrics()
    before = m.snapshot()
    items = {b"k%03d" % i: b"v" for i in range(20)}
    st = DeviceMerkleState.from_items(items.items())
    st.root_hash()
    st.apply([(b"k100", b"new")])
    st.root_hash()
    st.apply([(b"k005", b"upd")])
    st.root_hash()
    after = m.snapshot()

    def delta(name):
        return after["counters"].get(name, 0) - before["counters"].get(name, 0)

    def spans(name):
        return (after["spans"].get(name, {}).get("count", 0)
                - before["spans"].get(name, {}).get("count", 0))

    assert delta("device.structural_batches") == 1
    assert spans("device.restructure") == 1
    assert spans("device.key_index") == 2  # one per flush


@pytest.mark.parametrize("max_leaves,asked", [(10, 1), (64, 1), (65, 4), (200, 8)])
def test_ready_restructure_programs_counts_every_reachable_shape(
        max_leaves, asked):
    """From capacity 64 (one fresh-digest bucket per capacity): the
    restructure from each reachable capacity into itself and each larger
    one, and the root walk at each grown capacity; a keyspace that may
    shrink still readies the restructure within the capacity."""
    st = DeviceMerkleState.from_items((b"r%03d" % i, b"v") for i in range(60))
    assert st.ready_restructure_programs(max_leaves) == asked


@pytest.mark.parametrize("shards", [0, 2])
def test_ready_restructure_programs_leaves_nothing_to_compile(
        shards, monkeypatch):
    """Once the programs are ready, a restructure within the capacity, one
    that doubles it and one within the doubled capacity, and the publish
    after each, compile no tree program (only the leaf hashes of the new
    row counts), on the single-device tree and on a sharded one."""
    from merklekv_tpu.device.guard import DispatchGuard

    items = {b"r%03d" % i: b"v" for i in range(60)}  # capacity 64
    if shards:
        from merklekv_tpu.parallel.sharded_state import (
            ShardedDeviceMerkleState,
        )

        st = ShardedDeviceMerkleState.from_items(items.items(), shards=shards)
    else:
        st = DeviceMerkleState.from_items(items.items())
    st.root_hash()
    assert st.ready_restructure_programs(128) == 4
    compiled = []
    orig = DispatchGuard.compiled

    def spy(self, label, program, *args):
        n = len(self._executables)
        exe = orig(self, label, program, *args)
        if len(self._executables) > n:
            compiled.append(program.__name__)
        return exe

    monkeypatch.setattr(DispatchGuard, "compiled", spy)
    st.apply([(b"r0005x", b"in")])  # 61 keys, capacity 64
    items[b"r0005x"] = b"in"
    assert st.root_hash() == golden_root(items)
    grow = {b"s%03d" % i: b"g" for i in range(10)}  # 71 keys, capacity 128
    st.apply(list(grow.items()))
    items.update(grow)
    assert st.root_hash() == golden_root(items)
    assert st._capacity == 128
    st.apply([(b"r0100x", b"in")])  # 72 keys, capacity 128
    items[b"r0100x"] = b"in"
    assert st.root_hash() == golden_root(items)
    assert set(compiled) <= {"mkv_leaf_digests"}, compiled
