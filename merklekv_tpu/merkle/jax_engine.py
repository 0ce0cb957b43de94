"""TPU Merkle engine: whole-keyspace hashing and tree build as XLA programs.

Replaces the reference's per-insert full rebuild
(/root/reference/src/store/merkle.rs:52-56,73-121 — O(n^2 log n) hashing per
snapshot) with:

  1. one batched SHA-256 program over every leaf (``sha256_blocks``), and
  2. a log-depth bottom-up reduction (``build_levels_device``) whose per-level
     shapes are static under ``jit``, with the reference's odd-node promotion
     rule reproduced exactly so roots are bit-identical to the CPU core.

Two build paths:
- **static** (`tree_root`, `build_levels_device`): shapes specialized on the
  exact leaf count N. Best throughput; used by the bench and by snapshot-style
  rebuilds. One compile per distinct N.
- **capacity** (`tree_root_capacity`): one compiled program per capacity C
  (power-of-two bucket) valid for any live count n <= C, for serving paths
  where n changes per batch and recompiles are unacceptable. The dynamic level
  sizes are carried as traced scalars; promotion is a dynamic scatter.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from merklekv_tpu.merkle.packing import pack_leaf_groups
from merklekv_tpu.ops.sha256 import (
    digest_to_bytes,
    digests_to_bytes,
    sha256_blocks,
    sha256_node_pairs,
)

__all__ = [
    "leaf_digests",
    "build_levels_device",
    "tree_root",
    "tree_root_capacity",
    "anti_entropy_forward",
    "JaxMerkleTree",
]


def anti_entropy_forward(blocks, nblocks, digests, present):
    """The canonical single-chip data-plane step: hash every leaf, reduce to
    the tree root, and compute R-replica divergence — one jittable program.

    Shared by ``bench.py``, ``__graft_entry__.entry()``, and the sync
    manager so they all measure/compile the same forward program.

    blocks [N, B, 16] u32, nblocks [N] i32, digests [R, N, 8] u32,
    present [R, N] bool -> (root [8] u32, masks [R, N] bool, counts [R] i32).
    """
    from merklekv_tpu.merkle.diff import divergence_masks

    leaves = sha256_blocks(blocks, nblocks)
    root = build_levels_device(leaves)[-1][0]
    masks = divergence_masks(digests, present)
    counts = jnp.sum(masks, axis=1, dtype=jnp.int32)
    return root, masks, counts


def anti_entropy_forward_pallas(blocks, nblocks, digests, present):
    """Same program as :func:`anti_entropy_forward` with the SHA-256 work in
    Pallas kernels (rounds in VMEM). TPU-only; bit-identical outputs."""
    from merklekv_tpu.merkle.diff import divergence_masks
    from merklekv_tpu.ops.sha256_pallas import (
        leaf_digests_pallas,
        tree_root_pallas,
    )

    leaves = leaf_digests_pallas(blocks, nblocks)
    root = tree_root_pallas(leaves)
    masks = divergence_masks(digests, present)
    counts = jnp.sum(masks, axis=1, dtype=jnp.int32)
    return root, masks, counts


# ------------------------------------------------------------ leaf hashing

@lru_cache(maxsize=None)
def _leaf_fn(pallas: bool):
    """The jitted leaf-hash program for the dispatch backend: [N, B, 16]
    padded blocks + [N] valid counts -> [N, 8] digests. A fresh function
    per backend, so JAX's trace cache never replays the other backend's
    program (``hash_blocks`` re-reads the dispatch at trace time)."""
    del pallas  # cache key only
    from merklekv_tpu.ops.dispatch import hash_blocks

    @jax.jit
    def mkv_leaf_digests(blocks: jax.Array, nblocks: jax.Array):
        with jax.named_scope("mkv_leaf_digests"):
            return hash_blocks(blocks, nblocks)

    return mkv_leaf_digests


def leaf_digests(
    keys: Sequence[bytes],
    values: Sequence[bytes],
    label: Optional[str] = None,
) -> np.ndarray:
    """[N, 8] uint32 leaf digests (host array, input order) for N (key,
    value) pairs, hashed on device.

    The pairs are hashed in size-bucketed groups (``pack_leaf_groups``),
    one device call per group, so a few large values never pad the whole
    batch. Calls are dispatched asynchronously, so the device hashes one
    group while the host packs the next; the digests come back in one
    fetch at the end. Backend-dispatched: Pallas kernels on TPU
    (ops/dispatch.py), the scan formulation elsewhere — every caller
    (mirror warm build, incremental tree, sync leaf maps) gets the
    production path on the chip. With ``label``, each call and the fetch
    run under the dispatch guard with that label (programs compiled ahead
    of its deadline); host packing stays outside it."""
    from merklekv_tpu.device.guard import get_guard
    from merklekv_tpu.ops.dispatch import use_pallas

    keys, values = list(keys), list(values)
    program = _leaf_fn(use_pallas())
    guard = get_guard()
    parts = []
    for rows, packed in pack_leaf_groups(keys, values):
        args = (jnp.asarray(packed.blocks), jnp.asarray(packed.nblocks))
        digests = guard.call(label, program, *args) if label else program(*args)
        parts.append((rows, digests))

    def fetch() -> np.ndarray:
        out = np.zeros((len(keys), 8), np.uint32)
        for rows, digests in parts:
            out[rows] = np.asarray(digests)[: len(rows)]
        return out

    return guard.run(label, fetch) if label else fetch()


# ------------------------------------------------------------ static build

def build_levels_device(leaves: jax.Array) -> list[jax.Array]:
    """All tree levels, bottom-up, as device arrays. leaves: [N, 8] uint32.

    Trace-time Python loop — level sizes are static for a given N, so the
    whole tree is one straight-line XLA program of ~log2(N) batched hash
    calls. Odd trailing nodes are promoted (copied up) exactly like the
    reference (merkle.rs:111-114).
    """
    levels = [leaves]
    cur = leaves
    while cur.shape[0] > 1:
        m = cur.shape[0]
        pairs = m // 2
        nxt = sha256_node_pairs(cur[0 : 2 * pairs : 2], cur[1 : 2 * pairs : 2])
        if m % 2:
            nxt = jnp.concatenate([nxt, cur[-1:]], axis=0)
        levels.append(nxt)
        cur = nxt
    return levels


@jax.jit
def tree_root(leaves: jax.Array) -> jax.Array:
    """[8] uint32 root digest from [N, 8] leaf digests (N >= 1, static)."""
    return build_levels_device(leaves)[-1][0]


# jit-of-list-of-levels: one compile per leaf count N, then fast replays.
build_levels_jit = jax.jit(build_levels_device)


# ---------------------------------------------------------- capacity build

@jax.jit
def tree_root_capacity(leaves: jax.Array, n: jax.Array) -> jax.Array:
    """Root over the first ``n`` of C leaf slots; one compile per capacity C.

    leaves: [C, 8] uint32 with C a power of two (slots >= n are ignored);
    n: scalar int32, 1 <= n <= C. Produces the root of the odd-promotion tree
    of exactly n leaves — bit-identical to ``tree_root(leaves[:n])`` — so a
    serving path can reuse one compiled program for any live count within a
    capacity bucket.

    With C a power of two, every dynamic level size m <= C_level keeps the
    promotion slot m//2 strictly inside the next level's C_level/2 slots, so
    the dynamic scatter below never aliases a live pair slot.
    """
    c = leaves.shape[0]
    if c & (c - 1):
        raise ValueError(f"capacity must be a power of two, got {c}")
    cur = leaves
    m = jnp.asarray(n, jnp.int32)
    while cur.shape[0] > 1:
        half = cur.shape[0] // 2
        hashed = sha256_node_pairs(cur[0 : 2 * half : 2], cur[1 : 2 * half : 2])
        # Promote a dynamic odd tail: slot m//2 of the next level gets cur[m-1].
        odd = (m % 2) == 1
        last = jax.lax.dynamic_index_in_dim(
            cur, jnp.maximum(m - 1, 0), axis=0, keepdims=False
        )
        is_tgt = (jnp.arange(half, dtype=jnp.int32) == m // 2)[:, None] & odd
        promoted = jnp.where(is_tgt, last[None, :], hashed)
        # Levels past the top (m == 1) pass the root through unchanged.
        done = m <= 1
        cur = jnp.where(done, cur[:half], promoted)
        m = jnp.where(done, m, (m + 1) // 2)
    return cur[0]


# ------------------------------------------------------------ engine class

class JaxMerkleTree:
    """Same surface as the CPU ``MerkleTree`` with device-batched hashing.

    Mutations only touch a host-side (key -> (key_bytes, value_bytes)) map;
    ``root_hash``/``levels`` trigger one batched device rebuild. Used by the
    golden parity suite and as the serving engine's snapshot path.
    """

    def __init__(self) -> None:
        self._items: dict[bytes, bytes] = {}
        self._levels_np: Optional[list[np.ndarray]] = None

    # -- mutation ----------------------------------------------------------
    def insert(self, key: str | bytes, value: str | bytes) -> None:
        self._items[_b(key)] = _b(value)
        self._levels_np = None

    def remove(self, key: str | bytes) -> None:
        if self._items.pop(_b(key), None) is not None:
            self._levels_np = None

    def clear(self) -> None:
        if self._items:
            self._items.clear()
            self._levels_np = None

    def __len__(self) -> int:
        return len(self._items)

    # -- build -------------------------------------------------------------
    def _rebuild(self) -> None:
        if self._levels_np is not None:
            return
        if not self._items:
            self._levels_np = []
            return
        ordered = sorted(self._items.items())
        keys = [k for k, _ in ordered]
        values = [v for _, v in ordered]
        leaves = leaf_digests(keys, values)
        levels = build_levels_jit(leaves)
        self._levels_np = [np.asarray(lv) for lv in levels]

    @property
    def levels(self) -> list[np.ndarray]:
        self._rebuild()
        assert self._levels_np is not None
        return self._levels_np

    def root_hash(self) -> Optional[bytes]:
        self._rebuild()
        if not self._levels_np:
            return None
        return digest_to_bytes(self._levels_np[-1][0])

    def root_hex(self) -> str:
        r = self.root_hash()
        return r.hex() if r is not None else "0" * 64

    def inorder_keys(self) -> list[str]:
        return [k.decode("utf-8", "surrogateescape") for k in sorted(self._items)]

    def leaves(self) -> list[tuple[str, bytes]]:
        self._rebuild()
        assert self._levels_np is not None
        if not self._levels_np:
            return []
        hashes = digests_to_bytes(self._levels_np[0])
        return [
            (k.decode("utf-8", "surrogateescape"), h)
            for k, h in zip(sorted(self._items), hashes)
        ]


def _b(s: str | bytes) -> bytes:
    return s.encode("utf-8") if isinstance(s, str) else s
