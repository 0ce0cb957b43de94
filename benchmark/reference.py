"""The plain reference: a last-write-wins key-value model and a hashlib
Merkle chain over it.

It imports nothing of the program. Leaves are
``sha256(u32be(len k) + k + u32be(len v) + v)`` over keys in byte order;
parents ``sha256(l + r)``; an odd tail is promoted unchanged (the layout
the store documents for HASH and TREELEVEL).
"""

from __future__ import annotations

import hashlib
import struct

import numpy as np


def leaf(k: bytes, v: bytes) -> bytes:
    return hashlib.sha256(
        struct.pack(">I", len(k)) + k + struct.pack(">I", len(v)) + v
    ).digest()


def chain_levels(leaves: list[bytes]) -> list[list[bytes]]:
    """Every level of the tree over leaf digests in key order."""
    levels = [leaves]
    cur = leaves
    while len(cur) > 1:
        nxt = [hashlib.sha256(cur[i] + cur[i + 1]).digest()
               for i in range(0, len(cur) - 1, 2)]
        if len(cur) % 2:
            nxt.append(cur[-1])
        levels.append(nxt)
        cur = nxt
    return levels


def root_hex(items) -> str:
    """Root over ``(key, value)`` pairs in any order (at least one)."""
    ordered = sorted(items)
    return chain_levels([leaf(k, v) for k, v in ordered])[-1][0].hex()


class Model:
    """Last-write-wins state of one keyspace: every key holds the version
    of its latest acknowledged write (0 = the loaded record)."""

    # A key outside the dataset that the tree-lag probe writes.
    PROBE_KEY = b"bench_probe"

    def __init__(self, dataset) -> None:
        self.ds = dataset
        self.versions = np.zeros(dataset.n, np.int64)
        self.extra: dict[bytes, bytes] = {}  # keys outside the dataset
        # Values written in set-up to compile given shapes: key index ->
        # (version it was written at, value); a later version replaces it.
        self.explicit: dict[int, tuple[int, bytes]] = {}

    def values_of(self, idx: np.ndarray) -> list[bytes]:
        vals = self.ds.values_at(idx, self.versions[idx])
        for j, i in enumerate(idx.tolist()):
            e = self.explicit.get(i)
            if e is not None and e[0] == self.versions[i]:
                vals[j] = e[1]
        return vals

    def items(self):
        vals = self.values_of(np.arange(self.ds.n))
        return list(zip(self.ds.keys, vals)) + list(self.extra.items())

    def root_hex(self) -> str:
        return root_hex(self.items())
