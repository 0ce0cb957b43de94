"""The plain reference of a keyspace that grows by inserts: last-write-wins
over the loaded records and the records inserted after them, and the
hashlib Merkle chain over the keys present.

It imports nothing of the program: the leaf and the chain are
``benchmark/reference.py``'s (keys in byte order, ``sha256(l + r)``
parents, an odd tail promoted).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import chain_levels, leaf


class GrowingModel:
    """Every key of a ``GrowingDataset`` holds the version of its latest
    acknowledged write: 0 for a loaded record, 1 for an inserted one, -1
    while a key is not inserted yet (so an acknowledged insert is the
    maximum of the two, as for any write of a later version)."""

    # A key outside the dataset that the tree-lag probe writes.
    PROBE_KEY = b"bench_probe"

    def __init__(self, dataset) -> None:
        self.ds = dataset
        self.versions = np.full(len(dataset.keys), -1, np.int64)
        self.versions[: dataset.n] = 0
        self.extra: dict[bytes, bytes] = {}  # keys outside the dataset

    def values_of(self, idx: np.ndarray) -> list[bytes]:
        ver = self.versions[idx]
        if (ver < 0).any():
            raise KeyError("a value asked of a key never inserted")
        return self.ds.values_at(idx, ver)

    def items(self) -> list[tuple[bytes, bytes]]:
        idx = np.flatnonzero(self.versions >= 0)
        keys = [self.ds.keys[i] for i in idx.tolist()]
        return list(zip(keys, self.values_of(idx))) + list(self.extra.items())

    def root_hex(self) -> str:
        ordered = sorted(self.items())
        return chain_levels([leaf(k, v) for k, v in ordered])[-1][0].hex()
