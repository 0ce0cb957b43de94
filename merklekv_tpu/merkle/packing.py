"""Vectorized host-side packing of (key, value) leaves into SHA-256 blocks.

Variable-length keys/values must become fixed-shape tensors before the device
sees them. This module performs the length-prefixed leaf encoding
(``merklekv_tpu/merkle/encoding.py``; reference
/root/reference/src/store/merkle.rs:7-16) *and* the FIPS 180-4 padding in
fully vectorized numpy — no per-key Python loop — producing the
``[N, B, 16] uint32`` block tensor consumed by
:func:`merklekv_tpu.ops.sha256.sha256_blocks`.

Key and value bytes move as 2-D block copies, one per distinct (key
length, value length) class; the length prefixes, marker and bit length
are flat-index writes on one contiguous buffer.

A whole keyspace is packed through :func:`pack_leaf_groups`, never in one
``pack_leaves`` call: ``pack_leaves`` pads every row to the batch's longest
message, so a single 1 MiB value in a keyspace of 1 KB records would pad
every row to 1 MiB.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

__all__ = ["pack_leaves", "pack_leaf_groups", "PackedLeaves", "GROUP_BLOCKS"]

# Blocks (rows x padded width) one packed group holds: 64 MiB of message
# blocks, so host and device memory per hashing call follow this bound, not
# the keyspace size times its largest value.
GROUP_BLOCKS = 1 << 20


class PackedLeaves:
    """Fixed-shape SHA-256 input tensors for a batch of leaves.

    Attributes:
      blocks:  [N, B, 16] uint32 — padded message blocks, big-endian words.
      nblocks: [N] int32 — valid block count per leaf (>= 1).
    """

    __slots__ = ("blocks", "nblocks")

    def __init__(self, blocks: np.ndarray, nblocks: np.ndarray) -> None:
        self.blocks = blocks
        self.nblocks = nblocks

    @property
    def n(self) -> int:
        return self.blocks.shape[0]

    @property
    def max_blocks(self) -> int:
        return self.blocks.shape[1]


def _lengths(items: list[bytes]) -> np.ndarray:
    return np.fromiter((len(b) for b in items), dtype=np.int64, count=len(items))


def _block_counts(klens: np.ndarray, vlens: np.ndarray) -> np.ndarray:
    # 8 length-prefix bytes + 0x80 marker + 8-byte bit length, 64 B blocks.
    return (8 + klens + vlens + 9 + 63) // 64


def _pow2(x: np.ndarray) -> np.ndarray:
    """Elementwise next power of two (x >= 1)."""
    return np.left_shift(1, np.ceil(np.log2(x)).astype(np.int64))


def pack_leaf_groups(
    keys: list[bytes], values: list[bytes]
) -> Iterator[tuple[np.ndarray, PackedLeaves]]:
    """Pack N leaves as groups of similar size: yields ``(rows, packed)``
    covering every leaf exactly once.

    Leaves are bucketed by the power of two at or above their block count;
    each group is padded to its bucket's width and holds at most
    :data:`GROUP_BLOCKS` blocks. ``rows`` are the leaves' indices in the
    input (ascending within a group); ``packed`` has a power-of-two row
    count >= ``len(rows)``, its extra rows repeating the group's first leaf.
    So the shapes a caller hashes come from a small fixed set (one compile
    each), and memory follows the data rather than N x the largest value.
    """
    n = len(keys)
    if n != len(values):
        raise ValueError("keys and values must have equal length")
    if n == 0:
        return
    widths = _pow2(_block_counts(_lengths(keys), _lengths(values)))
    order = np.argsort(widths, kind="stable")
    sorted_w = widths[order]
    starts = np.flatnonzero(np.r_[True, sorted_w[1:] != sorted_w[:-1]])
    for s, e in zip(starts, np.r_[starts[1:], n]):
        width = int(sorted_w[s])
        per_group = max(1, GROUP_BLOCKS // width)
        for lo in range(s, e, per_group):
            rows = order[lo : min(lo + per_group, e)]
            pad = int(_pow2(np.array([len(rows)]))[0]) - len(rows)
            ks = [keys[i] for i in rows] + [keys[rows[0]]] * pad
            vs = [values[i] for i in rows] + [values[rows[0]]] * pad
            yield rows, pack_leaves(ks, vs, min_blocks=width)


def pack_leaves(
    keys: list[bytes],
    values: list[bytes],
    min_blocks: int = 1,
) -> PackedLeaves:
    """Pack N (key, value) leaves into padded SHA-256 block tensors.

    Message layout per leaf (then standard SHA-256 padding):
      u32_be(len(key)) || key || u32_be(len(value)) || value

    ``min_blocks`` lets callers force a common block-axis size across batches
    (e.g. to reuse one compiled program).
    """
    n = len(keys)
    if n != len(values):
        raise ValueError("keys and values must have equal length")
    if n == 0:
        return PackedLeaves(
            np.zeros((0, max(min_blocks, 1), 16), np.uint32),
            np.zeros((0,), np.int32),
        )

    klens = _lengths(keys)
    vlens = _lengths(values)
    mlens = 8 + klens + vlens
    nblocks = _block_counts(klens, vlens)
    max_b = int(max(nblocks.max(), min_blocks))
    row = max_b * 64

    out = np.zeros((n, row), dtype=np.uint8)
    flat = out.reshape(-1)
    row_starts = np.arange(n, dtype=np.int64) * row

    # Length prefixes: key length at offset 0, value length at 4 + klen.
    kl_be = klens.astype(">u4").view(np.uint8).reshape(n, 4)
    vl_be = vlens.astype(">u4").view(np.uint8).reshape(n, 4)
    for c in range(4):
        flat[row_starts + c] = kl_be[:, c]
        flat[row_starts + 4 + klens + c] = vl_be[:, c]

    # Key bytes at offset 4, value bytes at 8 + klen: one 2-D block copy
    # per distinct (key length, value length) class, whose rows share every
    # offset. (A per-byte index scatter moves 8 bytes of index per byte of
    # data.) A keyspace of fixed-size records has a handful of classes.
    cls = klens * (int(vlens.max()) + 1) + vlens
    order = np.argsort(cls, kind="stable")
    srt = cls[order]
    bounds = np.flatnonzero(np.r_[True, srt[1:] != srt[:-1], True])
    for s, e in zip(bounds[:-1], bounds[1:]):
        rows = order[s:e]
        kl, vl = int(klens[rows[0]]), int(vlens[rows[0]])
        if kl:
            out[rows, 4 : 4 + kl] = np.frombuffer(
                b"".join([keys[i] for i in rows]), np.uint8
            ).reshape(-1, kl)
        if vl:
            out[rows, 8 + kl : 8 + kl + vl] = np.frombuffer(
                b"".join([values[i] for i in rows]), np.uint8
            ).reshape(-1, vl)

    # 0x80 end-of-message marker.
    flat[row_starts + mlens] = 0x80

    # 64-bit big-endian bit length in the last 8 bytes of the final block.
    bl_be = (mlens * 8).astype(">u8").view(np.uint8).reshape(n, 8)
    tail = row_starts + nblocks * 64 - 8
    for c in range(8):
        flat[tail + c] = bl_be[:, c]

    words = out.view(">u4").astype(np.uint32).reshape(n, max_b, 16)
    return PackedLeaves(words, nblocks.astype(np.int32))
