"""ShardedDeviceMerkleState: the serving Merkle tree over the whole mesh.

The single-device ``DeviceMerkleState`` (merkle/incremental.py) keeps the
padded tree in one chip's HBM; this subclass keeps the keyspace-ordered
leaf array sharded across a device mesh with ``NamedSharding(mesh,
PartitionSpec("key"))`` and replaces only the device-dispatch seam:

- **build / restructure** run the explicit SPMD programs in
  parallel/sharded_merkle.py — per-shard subtree reduction in parallel,
  shard roots combined via one all_gather and the wide top tree (the
  parallel-first decomposition of arxiv 1604.04206 / 1607.00307);
- **incremental updates** are ROUTED PER SHARD on the host: the batch is
  grouped by target shard into a ``[D, kb, ...]`` tensor sharded on dim 0,
  so each device receives only its own sub-batch (padded rows scatter into
  a per-level scratch slot and vanish), hashes it, scatters it into its
  local leaf slice, and re-reduces only the touched parent paths.

The resulting level tuple has the SAME global layout as the single-device
padded tree (level j is ``[C >> j, 8]``; the bottom levels keyspace-sharded,
the top log2(D) levels replicated), so every query — root promotion-chain
walk, ``level_nodes`` TREELEVEL serving, staleness bookkeeping, the
PENDING_LIMIT staging contract — is inherited unchanged and answers
bit-identically to the single-device tree. That identity is the wire
compatibility promise: a walker cannot tell how many chips serve it.
"""

from __future__ import annotations

import sys
import time
from typing import Iterable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from merklekv_tpu.device.guard import get_guard
from merklekv_tpu.merkle.incremental import DeviceMerkleState, _bucket
from merklekv_tpu.merkle.jax_engine import leaf_digests
from merklekv_tpu.obs.metrics import get_metrics
from merklekv_tpu.ops.dispatch import use_pallas
from merklekv_tpu.parallel.mesh import make_mesh
from merklekv_tpu.parallel.sharded_merkle import (
    _local_level_count,
    sharded_levels_program,
    sharded_restructure_program,
    sharded_scatter_program,
)
from merklekv_tpu.utils.tracing import span

__all__ = ["ShardedDeviceMerkleState", "resolve_shard_count"]

_warned_clamp = False


def resolve_shard_count(mode, n_devices: Optional[int] = None) -> int:
    """``[device] sharding`` -> shard count.

    Returns 0 for the single-device backend ("off", or "auto" on a
    one-device host), else a power-of-two count: "auto" takes the largest
    power-of-two subset of the local devices; an explicit N is clamped to
    that subset (with a one-time warning) so an over-sized config degrades
    the mesh instead of killing the serving path.
    """
    mode = str(mode).strip().lower()
    if mode in ("off", "false", "0", "none", ""):
        return 0
    if n_devices is None:
        n_devices = len(jax.local_devices())
    avail = 1 << (max(1, n_devices).bit_length() - 1)
    if mode in ("auto", "true"):
        return avail if avail > 1 else 0
    d = int(mode)
    if d < 1 or d & (d - 1):
        raise ValueError(
            f"[device] sharding must be auto|off|power-of-two, got {mode!r}"
        )
    if d > avail:
        global _warned_clamp
        if not _warned_clamp:
            _warned_clamp = True
            print(
                f"[device] sharding={d} exceeds the local device complement "
                f"({n_devices}); clamping to {avail}",
                file=sys.stderr, flush=True,
            )
        return avail
    return d


class ShardedDeviceMerkleState(DeviceMerkleState):
    """Keyspace-sharded serving tree over a local device mesh.

    ``shards`` must be a power of two <= the local device count (1 runs the
    SPMD path over a one-device mesh — useful for parity tests); passing a
    prebuilt ``mesh`` reuses it instead. All host bookkeeping (sorted key
    array, pending staging, flush classification) and every query path are
    inherited from :class:`DeviceMerkleState`.
    """

    def __init__(
        self,
        shards: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        axis: str = "key",
        devices=None,
    ) -> None:
        if mesh is None:
            # LOCAL devices only: this state is a per-node serving
            # structure, not a cross-host SPMD program — non-addressable
            # devices of a multi-host jax cluster cannot back it.
            devs = list(devices) if devices is not None else jax.local_devices()
            # Default: the auto policy's mesh width, floored at a 1-device
            # mesh (the state itself is valid over one device; callers
            # wanting the plain single-device backend pass none of this).
            d = shards if shards is not None else max(
                1, resolve_shard_count("auto", len(devs))
            )
            if d < 1 or d & (d - 1):
                raise ValueError(
                    f"shard count must be a positive power of two, got {d}"
                )
            if d > len(devs):
                raise ValueError(
                    f"shard count {d} exceeds local device count {len(devs)}"
                )
            mesh = make_mesh({axis: d}, devices=devs[:d])
        self._mesh = mesh
        self._axis = axis
        super().__init__(sharding=NamedSharding(mesh, P(axis, None)))
        # Dispatch cost of the last sharded subtree rebuild (build or
        # restructure), microseconds — the device.shard_rebuild_us gauge.
        self.last_shard_rebuild_us = -1

    @classmethod
    def from_items(
        cls,
        items: Iterable[tuple[bytes, bytes]],
        shards: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        axis: str = "key",
        devices=None,
    ) -> "ShardedDeviceMerkleState":
        st = cls(shards=shards, mesh=mesh, axis=axis, devices=devices)
        dedup = dict(items)
        if dedup:
            ordered = sorted(dedup.items())
            st._initial_build(
                np.array([k for k, _ in ordered], dtype=object),
                [v for _, v in ordered],
            )
        return st

    @property
    def shard_count(self) -> int:
        return self._n_shards

    @property
    def _guard_prefix(self) -> str:
        """Dispatch-guard labels carry the shard width (``shard8_build``,
        ``shard2_scatter``, ...) so the chaos injector can fault ONE rung
        of the degradation ladder (``shard8_*``) or every sharded rung
        (``shard*``) while the single-device labels stay clean."""
        return f"shard{self._n_shards}_"

    # -------------------------------------------------- device dispatch
    def _put_routed(self, arr: np.ndarray) -> jax.Array:
        """[D, ...] per-shard-routed host array -> device, dim 0 on the
        mesh axis (each device receives only its own sub-batch)."""
        spec = P(self._axis, *(None,) * (arr.ndim - 1))
        return jax.device_put(arr, NamedSharding(self._mesh, spec))

    def _record_rebuild(self, t0: float) -> None:
        dt = time.perf_counter() - t0
        self.last_shard_rebuild_us = int(dt * 1e6)
        m = get_metrics()
        m.inc("device.shard_batches")
        # Async-enqueue semantics, like the device.scatter span: this is
        # trace+enqueue cost (queue pressure), not on-device execution.
        m.observe("device.shard_rebuild_dispatch", dt)

    def _dispatch_build(self, padded: np.ndarray) -> tuple:
        fn = sharded_levels_program(
            self._mesh, self._axis, len(padded), use_pallas()
        )
        t0 = time.perf_counter()
        levels = get_guard().call(self._label("build"), fn, self._put(padded))
        self._record_rebuild(t0)
        return levels

    def _restructure_program(self, c_old: int, c_new: int, kb: int):
        return sharded_restructure_program(
            self._mesh, self._axis, c_old, c_new, kb, use_pallas()
        )

    def _dispatch_restructure(
        self, gather_padded, fresh_pos, fresh, kb: int, c_new: int
    ) -> tuple:
        fn = self._restructure_program(self._capacity, c_new, kb)
        t0 = time.perf_counter()
        levels = get_guard().call(
            self._label("restructure"), fn, self._levels[0],
            self._put(gather_padded, one_d=True), jnp.asarray(fresh_pos),
            jnp.asarray(fresh),
        )
        self._record_rebuild(t0)
        return levels

    # ------------------------------------------- per-shard routed scatter
    def _update_in_place(self, items: list[tuple[bytes, bytes]]) -> None:
        """Value-only batch: hash the fresh leaves (size-bucketed, like the
        single-device path), route each digest to its owning shard on the
        host, then ONE SPMD dispatch scatters every shard's sub-batch in
        parallel (leaf scatter + parent-path re-reduce + top tree)."""
        k = len(items)
        d = self._n_shards
        l = self._capacity // d
        pos = self._positions([key for key, _ in items])
        with span("device.leaf_hash", log=False):
            fresh = leaf_digests(
                [key for key, _ in items], [v for _, v in items],
                label=self._label("scatter"),
            )
        shard = pos // l
        local = pos % l
        counts = np.bincount(shard, minlength=d)
        kb = _bucket(int(counts.max()))
        # Routed tensors: dim 0 is the shard. Pad rows keep the scratch
        # sentinel L as their index (the program drops them).
        idx = np.full((d, kb), l, np.int32)
        digests = np.zeros((d, kb, 8), np.uint32)
        order = np.argsort(shard, kind="stable")
        srt = shard[order]
        offs = np.arange(k) - np.searchsorted(srt, srt)
        idx[srt, offs] = local[order]
        digests[srt, offs] = fresh[order]

        n_local = _local_level_count(self._capacity, d)
        with span("device.scatter", log=False):
            fn = sharded_scatter_program(
                self._mesh, self._axis, self._capacity, kb, use_pallas()
            )
            self._levels = get_guard().call(
                self._label("scatter"), fn, *self._levels[:n_local],
                self._put_routed(idx), self._put_routed(digests),
            )
        self.incremental_batches += 1
        m = get_metrics()
        m.inc("device.scatter_keys", k)
        m.inc("device.scatter_bytes", int(digests.nbytes + idx.nbytes))
