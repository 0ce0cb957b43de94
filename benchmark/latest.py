"""YCSB's read-latest traffic (CoreWorkload D) from a seed: inserts that
grow the keyspace in key order, and reads skewed toward the newest record.

- ``GrowingDataset``: the loaded records plus room for the keys the
  schedule inserts, which continue the loaded keys' naming (``user<n>``,
  YCSB's ``insertorder=ordered``). An inserted key's first value is its
  version 1; version 0 is a loaded record, which it never had.
- ``skewed_latest``: YCSB's ``SkewedLatestGenerator``.
- ``read_latest``: open-loop (Poisson) GETs and inserts.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.data import _M64, Dataset, Schedule

THETA = 0.99  # YCSB's ZIPFIAN_CONSTANT


class GrowingDataset(Dataset):
    """``config``'s loaded records (``n`` of them) and ``inserts`` more
    keys after them. ``config`` (the attribute) counts both, so a load
    worker that rebuilds the dataset from it has every key."""

    def __init__(self, config: dict, seed: int, inserts: int) -> None:
        loaded = int(config["recordcount"])
        super().__init__(dict(config, recordcount=loaded + int(inserts)),
                         seed)
        self.n = loaded


def zeta_prefix(count: int, theta: float = THETA) -> np.ndarray:
    """``z[m]`` = sum over i = 1..m of 1 / i^theta (``z[0]`` = 0): YCSB's
    ``zeta`` of every item count up to ``count``."""
    return np.concatenate(
        [[0.0], np.cumsum(1.0 / np.arange(1, count + 1) ** theta)])


def latest_rank_cdf(r: np.ndarray, itemcount: int, items0: int,
                    zeta: np.ndarray, theta: float = THETA) -> np.ndarray:
    """P(rank <= r) for one draw of YCSB's ``ZipfianGenerator.nextLong
    (itemcount)`` built over ``items0`` items: the closed form of Gray et
    al.'s generator as YCSB codes it, for u uniform on [0, 1): rank 0 for
    u < 1 / zetan, rank 1 for u < (1 + 0.5^theta) / zetan, else
    floor(itemcount (eta u - eta + 1)^alpha), alpha = 1 / (1 - theta),
    with eta from the constructor's item count."""
    zetan = zeta[itemcount]
    eta = (1 - (2.0 / items0) ** (1 - theta)) / (1 - zeta[2] / zetan)
    r = np.asarray(r, np.float64)
    # u below which the formula's rank is at most r, and u below which
    # rank 0 or 1 is returned without it.
    body = (((r + 1) / itemcount) ** (1 - theta) - 1 + eta) / eta
    low = (1 + 0.5 ** theta) / zetan
    cdf = np.where(r >= 1, np.maximum(body, low),
                   1 / zetan + np.maximum(0.0, body - low))
    return np.clip(cdf, 0.0, 1.0)


def skewed_latest(rng: np.random.Generator, newest: np.ndarray,
                  items0: int, zeta: np.ndarray,
                  theta: float = THETA) -> np.ndarray:
    """One key index per entry of ``newest`` (the index of the newest
    record at that draw), as YCSB's ``SkewedLatestGenerator`` draws it:
    ``newest - ZipfianGenerator.nextLong(newest)``. The Zipfian is built
    over ``items0`` items; like YCSB's ``nextLong``, it grows its zeta to
    each draw's item count while eta keeps the constructor's count."""
    m = newest.astype(np.int64)
    zetan = zeta[m]
    eta = (1 - (2.0 / items0) ** (1 - theta)) / (1 - zeta[2] / zetan)
    u = rng.random(len(m))
    uz = u * zetan
    rank = (m * np.power(eta * u - eta + 1.0, 1.0 / (1.0 - theta))).astype(
        np.int64)
    rank = np.where(uz < 1.0 + 0.5 ** theta, 1, rank)
    rank = np.where(uz < 1.0, 0, rank)
    return m - rank


def read_latest(n: int, traffic: dict, seed: int, stream: int,
                t_from: float, t_to: float, rate: float) -> Schedule:
    """Poisson arrivals at ``rate`` over [t_from, t_to), each an insert
    with probability ``insert_proportion`` and otherwise a GET. The j-th
    insert (in due order) writes key ``n + j`` at version 1. A GET draws
    its key by ``skewed_latest`` over the records loaded and inserted
    before it (YCSB's ``SkewedLatestGenerator`` over the inserts
    acknowledged so far): one key's requests travel in due order on one
    connection (``conn_of_key``), so the node has applied the insert
    before it reads the GET. Loaded keys are never rewritten, so a GET
    expects version 0 of a loaded key and 1 of an inserted one."""
    rng = np.random.default_rng([seed & _M64, 200 + stream])
    expect = rate * (t_to - t_from)
    gaps = rng.exponential(1.0 / rate,
                           int(expect + 8 * math.sqrt(expect) + 64))
    due = t_from + np.cumsum(gaps)
    due = due[due < t_to]
    m = len(due)
    kind = (rng.random(m) < traffic["insert_proportion"]).astype(np.int8)
    inserted = np.cumsum(kind)  # inserts due at or before each request
    key = np.empty(m, np.int64)
    ins = kind == 1
    key[ins] = n + inserted[ins] - 1
    reads = ~ins
    newest = n - 1 + inserted[reads]
    key[reads] = skewed_latest(rng, newest, n - 1,
                               zeta_prefix(int(newest.max(initial=n))))
    ver = (key >= n).astype(np.int64)
    return Schedule(due, kind, key, ver)

