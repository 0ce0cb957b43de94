"""Device-resident Merkle state with incremental updates for every op kind.

The reference rebuilds its whole tree on every mutation
(/root/reference/src/store/merkle.rs:52-56) and never updates the tree from
replication events (TODO at replication.rs:312-316). Here the tree LIVES in
device HBM and change-event batches are applied as XLA programs:

- **value updates** (keyspace shape unchanged): hash the k changed leaves,
  scatter them into the capacity-padded leaf level, re-reduce only the
  touched parent paths — O(k log C) device work.
- **inserts / deletes** (shape changes): the sorted layout shifts, so the
  interior of the tree right of the first edit must re-reduce — but the
  surviving leaves' digests are already on device. The batch becomes: host
  computes the permutation (numpy index arithmetic, no hashing), device
  gathers surviving digests into their new slots, scatters the k fresh
  digests, and re-reduces all levels. Host hashing cost is O(k changed
  leaves), never O(n); the O(n) interior re-reduction is pure 64-byte
  SHA-256 compressions in one fused program.

Representation: a FULL binary tree at capacity C = 2^d (slots >= n hold a
zero sentinel). The reference tree pairs only live nodes and promotes odd
tails, so its levels differ from the padded tree's — but only on the right
spine: by induction, reference level l equals padded level l at every
position except the last. ``mkv_root_walk`` therefore recovers the bit-exact
reference root from one padded node per level and a chain of log2(C) node
hashes that carries the corrected last node ("promotion chain"), each hash
backend-dispatched like every other tree program's.

Host memory: only the sorted key array is kept (values are never stored —
fresh digests are computed from the (key, value) pairs each batch carries),
so a 10M-key tree costs the host one object array, not a value map.
"""

from __future__ import annotations

import time
from functools import lru_cache, partial
from typing import Iterable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from merklekv_tpu.device.guard import get_guard
from merklekv_tpu.merkle.jax_engine import leaf_digests
from merklekv_tpu.obs.metrics import get_metrics
from merklekv_tpu.ops.dispatch import (
    hash_node_level,
    hash_node_pairs,
    use_pallas,
)
from merklekv_tpu.ops.sha256 import digest_to_bytes, digests_to_bytes
from merklekv_tpu.utils.tracing import span

__all__ = ["DeviceMerkleState"]


def _next_pow2(n: int) -> int:
    return 1 << max(1, (n - 1).bit_length())


def _bucket(k: int) -> int:
    """Round a batch size up so one compiled program serves many sizes."""
    return _next_pow2(max(k, 16))


# A restructure pads its fresh digests to 4096 rows, or to the whole new
# capacity for a larger batch. Each restructure program re-reduces the
# whole tree, so scattering a few thousand padded rows costs nothing beside
# it, while every bucket is one more full-tree program to compile ahead.
_RESTRUCTURE_BUCKET = 4096


def _restructure_buckets(c_new: int) -> list[int]:
    """Every fresh-digest bucket a restructure into capacity ``c_new`` uses."""
    if c_new <= _RESTRUCTURE_BUCKET:
        return [c_new]
    return [_RESTRUCTURE_BUCKET, c_new]


def _reduce_levels(leaves: jax.Array) -> tuple:
    """All padded-tree levels bottom-up; trace-time loop, static shapes.
    Node hashing is backend-dispatched (Pallas on TPU, scan elsewhere)."""
    levels = [leaves]
    cur = leaves
    while cur.shape[0] > 1:
        # Adjacent-pair level hash: capacity is a power of two, so every
        # level is even and the contiguous level kernel applies throughout
        # (no odd-promotion tail in the padded tree).
        cur = hash_node_level(cur)
        levels.append(cur)
    return tuple(levels)


# The compiled-program caches below key on use_pallas() so a backend flip
# between traces (tests forcing MKV_SHA256_BACKEND) can't replay a program
# compiled for the other formulation. Every call site runs its program
# through the dispatch guard's ahead-of-time compile (device/guard.py).
# Each program is named ``mkv_<what>`` and its body sits in a named scope
# of that name, so a profiler trace attributes its device ops by name.

@lru_cache(maxsize=None)
def _build_fn(capacity: int, pallas: bool):
    """Compiled initial build over capacity-padded leaves: one compile per
    capacity bucket, shared by every live count within it (the caller pads
    the digest array to C on the host)."""
    del pallas  # cache key only; _reduce_levels re-reads the dispatch

    @jax.jit
    def mkv_build_levels(leaves: jax.Array):
        with jax.named_scope("mkv_build_levels"):
            return _reduce_levels(leaves)

    return mkv_build_levels


def _scatter_levels(levels: tuple, idx: jax.Array, new_leaves: jax.Array):
    """Scatter new leaf digests + re-reduce only the touched parent paths.

    idx [kb] int32 (padded entries duplicate a real entry with the
    identical leaf value, so duplicate scatters are benign);
    new_leaves [kb, 8] uint32."""
    out = [levels[0].at[idx].set(new_leaves)]
    cur_idx = idx
    for lvl in range(1, len(levels)):
        cur_idx = cur_idx // 2
        left = out[-1][2 * cur_idx]
        right = out[-1][2 * cur_idx + 1]
        parents = hash_node_pairs(left, right)
        out.append(levels[lvl].at[cur_idx].set(parents))
    return tuple(out)


@lru_cache(maxsize=None)
def _scatter_fn(capacity: int, kb: int, pallas: bool):
    """Compiled leaf scatter + path re-reduction for one padded batch of kb
    fresh leaf digests: one compile per (capacity, kb) bucket. The digests
    are hashed before it by ``leaf_digests`` in size-bucketed groups, so a
    new value size never compiles a new scatter program."""
    del pallas

    @jax.jit
    def mkv_scatter(levels: tuple, idx: jax.Array, new_leaves: jax.Array):
        with jax.named_scope("mkv_scatter"):
            return _scatter_levels(levels, idx, new_leaves)

    return mkv_scatter


@lru_cache(maxsize=None)
def _restructure_fn(c_old: int, c_new: int, kb: int, pallas: bool):
    """Compiled gather + scatter + full reduction for shape changes.

    gather_idx [c_new] int32: source slot in the OLD leaf level for each new
    slot, or -1 for slots that receive a fresh digest / stay zero.
    fresh_pos [kb] int32 + fresh [kb, 8]: the k changed/inserted digests
    (padded entries duplicate entry 0 — same value, benign).
    """
    del pallas

    @jax.jit
    def mkv_restructure(old_leaves, gather_idx, fresh_pos, fresh):
        with jax.named_scope("mkv_restructure"):
            safe = jnp.clip(gather_idx, 0, max(c_old - 1, 0))
            base = jnp.where((gather_idx >= 0)[:, None], old_leaves[safe], 0)
            if kb:
                base = base.at[fresh_pos].set(fresh)
            return _reduce_levels(base)

    return mkv_restructure


@lru_cache(maxsize=None)
def _ref_root_fn(capacity: int, pallas: bool, mesh=None):
    """Compiled promotion-chain walk: padded levels + live count n -> the
    reference odd-promotion root over the first n leaves.

    The level sizes, and so the one padded node each level may combine,
    depend on n alone: the walk reads those log2(C) rows first, then runs
    the chain of node hashes on [1, 8] state, each through the backend
    dispatch (the Pallas node kernel on TPU). Every level hashes and a
    select keeps the result or the promoted tail, so n stays traced: one
    compile per capacity, whatever the live count.

    ``mesh`` is the mesh a sharded tree's levels live on. A Pallas kernel
    cannot be partitioned automatically, so there each hash runs under
    ``shard_map`` on every device's replicated copy of its [1, 8] rows."""
    node_hash = hash_node_pairs
    if pallas and mesh is not None:
        node_hash = jax.shard_map(
            hash_node_pairs, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False,
        )

    @jax.jit
    def mkv_root_walk(levels: tuple, n: jax.Array):
        with jax.named_scope("mkv_root_walk"):
            m = jnp.asarray(n, jnp.int32)
            last = jax.lax.dynamic_index_in_dim(
                levels[0], jnp.maximum(m - 1, 0), axis=0, keepdims=False
            )
            sizes, prevs = [], []
            for lvl in range(1, len(levels)):
                sizes.append(m)
                # Even m: reference's next last = H(level[m-2], last).
                # Position m-2 of the reference level equals the padded
                # level (only the last position can differ).
                prevs.append(jax.lax.dynamic_index_in_dim(
                    levels[lvl - 1], jnp.maximum(m - 2, 0), axis=0,
                    keepdims=False,
                ))
                m = (m + 1) // 2
            for m, prev in zip(sizes, prevs):
                combined = node_hash(prev[None], last[None])[0]
                # Odd m (m == 1 at and above the root): the tail is
                # promoted unchanged.
                last = jnp.where(m % 2 == 1, last, combined)
            return last

    return mkv_root_walk


@partial(jax.jit, static_argnames="width")
def mkv_level_gather(level: jax.Array, start: jax.Array, width: int):
    """``width`` rows of one padded level from a traced ``start``: one
    compiled gather per (level, width) however many slices are asked."""
    with jax.named_scope("mkv_level_gather"):
        return jax.lax.dynamic_slice_in_dim(level, start, width)


class DeviceMerkleState:
    """Sorted keyspace + device-resident padded tree levels.

    Host side owns only the sorted key array (the authoritative KV store is
    the native engine). Device side owns ``levels``: levels[0] is [C, 8]
    leaf digests, levels[d] is [1, 8].

    ``sharding`` (a ``NamedSharding`` whose spec shards dim 0, e.g.
    ``P("key", None)``) places the leaf level across a device mesh; the
    jitted build/scatter/restructure programs then run SPMD with XLA
    inserting the collectives (GSPMD) — the serving-path integration of
    SURVEY §2.4's keyspace sharding. Capacity is kept a multiple of the
    mesh axis so the leaf dimension always divides evenly.
    """

    # Auto-flush ceiling: bounds the host memory pending values can hold.
    PENDING_LIMIT = 65536

    # Dispatch-guard label prefix: every device program call routes through
    # the process guard (merklekv_tpu.device.guard) under a label naming
    # the seam — the chaos injector matches on it and the degradation
    # ladder reads it out of the typed error. The sharded subclass prefixes
    # its shard width so faults can target one rung.
    _guard_prefix = ""

    def _label(self, op: str) -> str:
        return self._guard_prefix + op

    def __init__(self, sharding=None) -> None:
        self._keys = np.empty(0, dtype=object)  # sorted key bytes
        # key -> sorted position. np.searchsorted on an OBJECT array does a
        # Python-level comparison per probe (~tens of ms per 32K-key batch
        # against a 1M tree) and was the sustained-update bottleneck; dict
        # lookups are O(1) C-level. Rebuilt on structural changes only.
        self._index: dict[bytes, int] = {}
        self._levels: Optional[tuple[jax.Array, ...]] = None
        self._capacity = 0
        self._sharding = sharding
        if sharding is not None:
            axis = sharding.spec[0]
            if not isinstance(axis, str):
                raise ValueError(
                    "sharding must shard dim 0 on a named mesh axis"
                )
            self._n_shards = int(sharding.mesh.shape[axis])
            if self._n_shards & (self._n_shards - 1):
                # Capacity is a power of two (the padded-tree math depends
                # on it), so only power-of-two shard counts divide the leaf
                # dimension evenly. Callers with odd device counts should
                # mesh a power-of-two subset (DeviceTreeMirror does).
                raise ValueError(
                    f"sharded tree needs a power-of-two shard count, "
                    f"got {self._n_shards}"
                )
            from jax.sharding import NamedSharding, PartitionSpec

            # Matching 1-D placement for per-slot index vectors.
            self._sharding_1d = NamedSharding(
                sharding.mesh, PartitionSpec(axis)
            )
        else:
            self._n_shards = 1
            self._sharding_1d = None
        # Writes accumulate here and flush as ONE device batch at the next
        # query (or at PENDING_LIMIT): a stream of N single-key applies
        # costs one restructure, not N — the amortization a per-write
        # caller (the mirror's remote-apply path) depends on.
        self._pending: dict[bytes, Optional[bytes]] = {}
        self.full_rebuilds = 0
        self.incremental_batches = 0
        self.structural_batches = 0

    # ------------------------------------------------------------ loading
    @classmethod
    def from_items(
        cls, items: Iterable[tuple[bytes, bytes]], sharding=None
    ) -> "DeviceMerkleState":
        st = cls(sharding=sharding)
        dedup = dict(items)
        if dedup:
            ordered = sorted(dedup.items())
            st._initial_build(
                np.array([k for k, _ in ordered], dtype=object),
                [v for _, v in ordered],
            )
        return st

    def __len__(self) -> int:
        self._flush()
        return len(self._keys)

    def leaf_count(self) -> int:
        """Built leaf count WITHOUT flushing staged changes — the gauge
        path must never trigger device work."""
        return len(self._keys)

    # ------------------------------------------------------------ lookups
    def _find(self, key: bytes) -> int:
        """Position of key in the sorted array, or -1."""
        return self._index.get(key, -1)

    def _positions(self, keys: Sequence[bytes]) -> np.ndarray:
        """Sorted-array positions for keys known to be present."""
        idx = self._index
        return np.fromiter(
            (idx[k] for k in keys), dtype=np.int32, count=len(keys)
        )

    def _set_keys(self, keys_arr: np.ndarray) -> None:
        self._keys = keys_arr
        self._index = {k: i for i, k in enumerate(keys_arr)}

    # ------------------------------------------------------------ updates
    def apply(self, changes: Sequence[tuple[bytes, Optional[bytes]]]) -> None:
        """Stage (key, value|None-for-delete) changes; last write per key
        wins. Device work is deferred to the next flush (the mirror's pump
        cycle, or the next exact query) so bursts of single-key applies
        amortize into one batch."""
        for k, v in changes:
            self._pending[k] = v
        if len(self._pending) >= self.PENDING_LIMIT:
            self._flush()

    def pending_count(self) -> int:
        """Staged-but-undispatched changes (no device work to read it)."""
        return len(self._pending)

    def flush_pending(self) -> None:
        """Dispatch every staged change to the device now — the pump's
        drain step. Idempotent when nothing is staged."""
        self._flush()

    def _flush(self) -> None:
        if not self._pending:
            return
        pending, self._pending = self._pending, {}
        try:
            self._flush_batch(pending)
        except BaseException:
            # A failed dispatch must not silently drop the batch: the tree
            # is unchanged (the dispatch seams assign levels atomically on
            # success), so restoring the staged changes keeps the state
            # consistent for a retry — or for the degradation ladder's
            # rebuild at a lower rung. Entries staged by a racing caller
            # between the swap and here win over the restored batch.
            merged = dict(pending)
            merged.update(self._pending)
            self._pending = merged
            raise

    def _flush_batch(self, pending: dict[bytes, Optional[bytes]]) -> None:
        t0 = time.perf_counter()
        with span("device.key_index", log=False):
            # One membership pass (O(1) dict probes) classifies the whole
            # batch; a structural batch also plans its new key array here.
            keys = sorted(pending)
            idx = self._index
            deletes = [k for k in keys if k in idx and pending[k] is None]
            inserts = [
                k for k in keys if k not in idx and pending[k] is not None
            ]
            upserts = {k: v for k, v in pending.items() if v is not None}
            structural = bool(deletes or inserts)
            if structural:
                plan = self._plan_restructure(deletes, inserts)

        if not structural:
            updates = sorted(upserts.items())
            if updates and self._levels is not None:
                self._update_in_place(updates)
            return
        self._restructure(*plan, upserts)
        # Not a ``with`` block: a batch is known to be structural only
        # after its lookup, which this span includes.
        get_metrics().observe_span(
            "device.restructure", time.perf_counter() - t0
        )

    def _update_in_place(self, items: list[tuple[bytes, bytes]]) -> None:
        k = len(items)
        kb = _bucket(k)
        idx = np.empty(kb, np.int32)
        idx[:k] = self._positions([key for key, _ in items])
        idx[k:] = idx[0]  # pad with a duplicate of a real entry
        # Pad rows repeat row 0's digest, so duplicate scatters write
        # identical values.
        digests = np.empty((kb, 8), np.uint32)
        with span("device.leaf_hash", log=False):
            digests[:k] = leaf_digests(
                [key for key, _ in items], [v for _, v in items],
                label=self._label("scatter"),
            )
        digests[k:] = digests[0]
        # JAX dispatch is asynchronous: this span times the enqueue; the
        # device's work on it completes inside the next root walk's fetch.
        with span("device.scatter", log=False):
            fn = _scatter_fn(self._capacity, kb, use_pallas())
            self._levels = get_guard().call(
                self._label("scatter"), fn, self._levels, jnp.asarray(idx),
                jnp.asarray(digests),
            )
        self.incremental_batches += 1
        m = get_metrics()
        m.inc("device.scatter_keys", k)
        m.inc("device.scatter_bytes", int(digests.nbytes + idx.nbytes))

    # ------------------------------------------------------------ structure
    def _capacity_for(self, n: int) -> int:
        # Sharded trees keep C a multiple of the mesh axis so the leaf
        # dimension always divides evenly across devices.
        return max(_next_pow2(n), self._n_shards)

    def _put(self, arr: np.ndarray, one_d: bool = False) -> jax.Array:
        """Host array -> device, honoring the keyspace sharding if set."""
        if self._sharding is None:
            return jnp.asarray(arr)
        return jax.device_put(
            arr, self._sharding_1d if one_d else self._sharding
        )

    def _initial_build(self, keys_arr: np.ndarray, values: list) -> None:
        n = len(keys_arr)
        c = self._capacity_for(n)
        # Full rebuilds are rare (warm-up, empty->non-empty restructure) and
        # expensive — a span records batch size and transfer bytes per the
        # device-plane attribution the MTU throughput analysis needs.
        with span("device.rebuild", keys=n, capacity=c) as rec:
            # Leaf hashing dispatches run guarded under the build label,
            # like every other program call: a wedged device must not hang
            # the warm thread with no deadline.
            digests = leaf_digests(
                list(keys_arr), values, label=self._label("build")
            )
            padded = np.zeros((c, 8), np.uint32)
            padded[:n] = digests
            rec["bytes"] = int(padded.nbytes)
            self._levels = self._dispatch_build(padded)
        self._set_keys(keys_arr)
        self._capacity = c
        self.full_rebuilds += 1

    def _plan_restructure(
        self, deletes: list[bytes], inserts: list[bytes]
    ) -> tuple[np.ndarray, dict[bytes, int], np.ndarray]:
        """Host plan of a structural batch: pure index arithmetic, no
        hashing of survivors. Returns the new sorted key array, its
        key -> position index, and the source slot in the old leaf level of
        each new slot (-1: fresh)."""
        old = self._keys
        n_old = len(old)
        del_pos = self._positions(deletes)
        survivors = np.delete(old, del_pos) if len(del_pos) else old
        surv_src = (
            np.delete(np.arange(n_old, dtype=np.int32), del_pos)
            if len(del_pos)
            else np.arange(n_old, dtype=np.int32)
        )
        ins_keys = np.array(sorted(inserts), dtype=object)
        if len(ins_keys):
            ins_at = np.searchsorted(survivors, ins_keys).astype(np.int64)
            new_keys = np.insert(survivors, ins_at, ins_keys)
            gather = np.insert(surv_src, ins_at, np.int32(-1))
        else:
            new_keys = survivors
            gather = surv_src
        return new_keys, {k: i for i, k in enumerate(new_keys)}, gather

    def _restructure(
        self,
        new_keys: np.ndarray,
        new_index: dict[bytes, int],
        gather: np.ndarray,
        upserts: dict[bytes, Optional[bytes]],
    ) -> None:
        n_new = len(new_keys)
        if n_new == 0:
            self._set_keys(np.empty(0, dtype=object))
            self._levels = None
            self._capacity = 0
            return
        if self._levels is None:
            # Empty -> non-empty: everything is fresh; all values are in
            # this batch by construction.
            self._initial_build(
                new_keys, [upserts[k] for k in new_keys]
            )
            return

        c_new = self._capacity_for(n_new)
        gather_padded = np.full(c_new, -1, np.int32)
        gather_padded[:n_new] = gather

        # Fresh digests: every upsert (update of a survivor or insert).
        fresh_items = sorted(upserts.items())
        k = len(fresh_items)
        # k <= n_new <= c_new, so the last bucket always fits.
        kb = next(b for b in _restructure_buckets(c_new) if b >= k) if k else 0
        if k:
            fresh_pos = np.empty(kb, np.int32)
            fresh_pos[:k] = [new_index[key] for key, _ in fresh_items]
            fresh_pos[k:] = fresh_pos[0]

            # Guarded like the build path: the fresh-digest leaf hashing
            # is a device dispatch and must not be able to wedge the
            # pump thread outside the deadline.
            fresh = np.empty((kb, 8), np.uint32)
            with span("device.leaf_hash", log=False):
                fresh[:k] = leaf_digests(
                    [key for key, _ in fresh_items],
                    [v for _, v in fresh_items],
                    label=self._label("restructure"),
                )
            fresh[k:] = fresh[0]
        else:
            fresh_pos = np.zeros(0, np.int32)
            fresh = np.zeros((0, 8), np.uint32)

        self._levels = self._dispatch_restructure(
            gather_padded, fresh_pos, fresh, kb, c_new
        )
        self._keys, self._index = new_keys, new_index
        self._capacity = c_new
        self.structural_batches += 1
        m = get_metrics()
        m.inc("device.structural_batches")
        m.inc("device.restructure_keys", k)
        m.inc("device.restructure_bytes",
              int(gather_padded.nbytes + fresh_pos.nbytes + k * 32))

    def ready_restructure_programs(self, max_leaves: int) -> int:
        """Compile ahead, through the dispatch guard (nothing runs), every
        program a structural flush can reach while the tree grows from its
        capacity C up to the capacity that holds ``max_leaves`` keys: the
        restructure from each such capacity into itself and into each
        larger one, at every fresh-digest bucket, and the root walk at each
        capacity above C. Returns how many programs were asked for (0 while
        the tree is empty)."""
        if self._levels is None:
            return 0
        guard, c, asked = get_guard(), self._capacity, 0
        top = self._capacity_for(max_leaves)
        mesh = None if self._sharding is None else self._sharding.mesh
        # Leaf level a restructure starts from, by capacity: the built one,
        # then each grown one as the shape (and sharding) its program gives.
        leaves = {c: self._levels[0]}
        c_new = c
        while c_new <= max(c, top):
            gather = self._put(np.zeros(c_new, np.int32), one_d=True)
            for c_old in sorted(leaves) + [c_new] * (c_new not in leaves):
                for kb in _restructure_buckets(c_new):
                    exe = guard.compiled(
                        self._label("restructure"),
                        self._restructure_program(c_old, c_new, kb),
                        leaves[c_old], gather,
                        jnp.zeros(kb, jnp.int32),
                        jnp.zeros((kb, 8), jnp.uint32),
                    )
                    asked += 1
                if c_new not in leaves:
                    grown = tuple(
                        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
                        for s, sh in zip(exe.out_info, exe.output_shardings)
                    )
                    leaves[c_new] = grown[0]
                    guard.compiled(
                        self._label("root"),
                        _ref_root_fn(c_new, use_pallas(), mesh),
                        grown, jnp.int32(1),
                    )
                    asked += 1
            c_new *= 2
        return asked

    # ------------------------------------------------- device dispatch seam
    # The host planning above (classification, permutation index arithmetic,
    # packing) is backend-agnostic; only these hooks touch a compiled
    # device program. ShardedDeviceMerkleState (parallel/sharded_state.py)
    # overrides them with explicit shard_map SPMD programs.
    def _dispatch_build(self, padded: np.ndarray) -> tuple:
        """Capacity-padded [C, 8] leaf digests -> every padded level."""
        fn = _build_fn(len(padded), use_pallas())
        return get_guard().call(self._label("build"), fn, self._put(padded))

    def _restructure_program(self, c_old: int, c_new: int, kb: int):
        """The jitted gather + scatter + full reduction for one shape
        change (capacity ``c_old`` -> ``c_new``, ``kb`` fresh digests)."""
        return _restructure_fn(c_old, c_new, kb, use_pallas())

    def _dispatch_restructure(
        self,
        gather_padded: np.ndarray,
        fresh_pos: np.ndarray,
        fresh: np.ndarray,
        kb: int,
        c_new: int,
    ) -> tuple:
        """Gather survivors into shifted slots + scatter fresh digests +
        full re-reduction (``self._capacity`` still holds the OLD C)."""
        fn = self._restructure_program(self._capacity, c_new, kb)
        return get_guard().call(
            self._label("restructure"), fn, self._levels[0],
            self._put(gather_padded, one_d=True), jnp.asarray(fresh_pos),
            jnp.asarray(fresh),
        )

    # ------------------------------------------------------------ queries
    def root_hash(self, flush: bool = True) -> Optional[bytes]:
        """Reference-tree root. ``flush=False`` serves the tree AS BUILT —
        staged changes stay staged — so a bounded-staleness reader (the
        mirror's published snapshot) never triggers device work beyond the
        root walk itself."""
        if flush:
            self._flush()
        if not len(self._keys) or self._levels is None:
            return None
        mesh = None if self._sharding is None else self._sharding.mesh
        fn = _ref_root_fn(self._capacity, use_pallas(), mesh)
        return digest_to_bytes(get_guard().call(
            self._label("root"), fn, self._levels,
            jnp.int32(len(self._keys)), fetch=True,
        ))

    def root_hex(self, flush: bool = True) -> str:
        r = self.root_hash(flush=flush)
        return r.hex() if r is not None else "0" * 64

    def leaf_digest(self, key: bytes) -> Optional[bytes]:
        self._flush()
        i = self._find(key)
        if i < 0 or self._levels is None:
            return None
        return get_guard().run(
            self._label("levels"),
            lambda: digest_to_bytes(np.asarray(self._levels[0][i])),
        )

    # ------------------------------------------- reference-level serving
    @staticmethod
    def ref_level_sizes(n: int) -> list[int]:
        """Reference (odd-promotion) tree level sizes for ``n`` leaves
        (shared size law — see merkle/cpu.py)."""
        from merklekv_tpu.merkle.cpu import ref_level_sizes

        return ref_level_sizes(n)

    def _promoted_last(self, level: int) -> bytes:
        """The reference tree's LAST node at ``level``, recovered from the
        padded levels by the promotion-chain walk (same recurrence as
        ``_ref_root_fn``, stopped at ``level``): the padded tree hashes
        zero sentinels into its right spine, so only this one position per
        level can differ from the reference tree."""
        n = len(self._keys)
        last = digest_to_bytes(np.asarray(self._levels[0][n - 1]))
        m = n
        for lvl in range(1, level + 1):
            if m <= 1:
                break
            if m % 2 == 0:
                # Even level size: the reference's next last node combines
                # position m-2 (identical in the padded tree — only the
                # LAST position per level can differ) with the carried
                # correction. Odd sizes promote the tail unchanged.
                from merklekv_tpu.merkle.encoding import node_hash

                prev = digest_to_bytes(
                    np.asarray(self._levels[lvl - 1][m - 2])
                )
                last = node_hash(prev, last)
            m = (m + 1) // 2
        return last

    def level_nodes(
        self, level: int, lo: int, hi: int, flush: bool = True
    ) -> tuple[list[tuple[int, bytes]], int]:
        """Reference-tree digests at ``level`` for indices ``[lo, hi)``
        (clamped to the level's size), plus the live leaf count — the
        device-side answer to the TREELEVEL wire verb. One batched device
        gather serves the whole slice; the only host hashing is the O(level)
        promotion-chain correction when the slice touches the level's last
        node. Digests are bit-identical to the reference tree (and hence to
        the native server's host fallback). ``flush=False`` serves the tree
        as built (the published-snapshot read path)."""
        if flush:
            self._flush()
        n = len(self._keys)
        if n == 0 or self._levels is None:
            return [], 0
        sizes = self.ref_level_sizes(n)
        if level >= len(sizes):
            return [], n
        m = sizes[level]
        lo = max(0, min(lo, m))
        hi = max(lo, min(hi, m))
        if lo == hi:
            return [], n

        # One device gather for the whole slice (the padded level's prefix
        # matches the reference level everywhere but the last position);
        # guarded so a TREELEVEL serve against a wedged device fails at the
        # dispatch deadline (and the native fallback answers) instead of
        # parking the query thread forever.
        def read() -> list[tuple[int, bytes]]:
            # A power-of-two window at a traced offset: one compiled gather
            # per (level, window) however many distinct slices walkers ask
            # for, where a static [lo:hi] slice compiles per request.
            arr = self._levels[level]
            width = min(_next_pow2(hi - lo), arr.shape[0])
            start = min(lo, arr.shape[0] - width)
            window = np.asarray(
                mkv_level_gather(arr, np.int32(start), width)
            )
            digs = digests_to_bytes(window[lo - start : hi - start])
            rows = [(lo + i, d) for i, d in enumerate(digs)]
            if hi == m and level > 0:
                rows[-1] = (m - 1, self._promoted_last(level))
            return rows

        return get_guard().run(self._label("levels"), read), n
