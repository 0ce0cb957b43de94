"""Data and traffic generation from a seed.

Everything a run sends is a function of the configuration file, the
traffic file and ``--seed``: the keys, each key's initial value, every
request's due time, kind, key and written version, and the bytes of every
value ever written. The program under test receives only the generated
requests.

Value model: ``value(i, ver)`` is the ``ver``-th value written to key ``i``
(``ver`` 0 is the loaded record). It is a slice of a seeded pool of
alphanumeric bytes, at an offset mixed from ``(i, ver)``, so two versions
of a key differ and the generator, the reference and the checker can each
rebuild any value without storing it.

Sizes do not depend on the seed: key ``i`` has the same key and loaded
value size under every seed, so seeds change which bytes and which keys a
run touches, not how much work the load is.
"""

from __future__ import annotations

import math

import numpy as np

_ALNUM = np.frombuffer(
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789", np.uint8
)
POOL_BYTES = 1 << 23
_M64 = (1 << 64) - 1
# A fixed stream for sizes, so every seed gets the same sizes.
_SIZE_SEED = 0x5EED51

# YCSB's ScrambledZipfianGenerator: a Zipfian over 10^10 items with its
# precomputed zeta, hashed (FNV-1a 64) onto the record count.
_YCSB_ITEMS = 10_000_000_000
_YCSB_ZETAN = 26.46902820178302
_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 1099511628211


def mix64(x):
    """splitmix64 finaliser over Python ints or uint64 arrays."""
    if isinstance(x, np.ndarray):
        z = x.astype(np.uint64, copy=True)
        with np.errstate(over="ignore"):
            z += np.uint64(0x9E3779B97F4A7C15)
            z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))
    z = (x + 0x9E3779B97F4A7C15) & _M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return z ^ (z >> 31)


def fnv1a64_long(v: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` over each value's 8 little-endian octets,
    with Java's ``Math.abs`` of the signed result."""
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    x = v.astype(np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= x & np.uint64(0xFF)
            h *= np.uint64(_FNV_PRIME)
            x >>= np.uint64(8)
    s = h.view(np.int64)
    return np.where(s < 0, -(s + 1) + 1, s).astype(np.uint64)


def _zeta(n: int, theta: float) -> float:
    return sum(1.0 / (i ** theta) for i in range(1, n + 1))


def scrambled_zipfian(rng: np.random.Generator, count: int, records: int,
                      theta: float = 0.99) -> np.ndarray:
    """``count`` record indices drawn as YCSB's scrambled Zipfian does
    (Gray et al.'s rejection-free Zipfian over 10^10 items, then FNV)."""
    if theta != 0.99:
        raise ValueError("the precomputed zeta holds for theta 0.99 only")
    n = _YCSB_ITEMS
    zeta2 = _zeta(2, theta)
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / n) ** (1 - theta)) / (1 - zeta2 / _YCSB_ZETAN)
    u = rng.random(count)
    uz = u * _YCSB_ZETAN
    rank = (n * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    rank = np.where(uz < 1.0 + 0.5 ** theta, 1, rank)
    rank = np.where(uz < 1.0, 0, rank)
    return (fnv1a64_long(rank) % np.uint64(records)).astype(np.int64)


def gev_quantile(u, loc: float, scale: float, shape: float):
    """mutilate's ``GEV::generate``: loc + scale((-ln U)^-shape - 1)/shape."""
    return loc + scale * (np.power(-np.log(u), -shape) - 1.0) / shape


def gpareto_quantile(u, loc: float, scale: float, shape: float):
    """mutilate's ``GPareto::generate``: loc + scale(U^-shape - 1)/shape."""
    return loc + scale * (np.power(u, -shape) - 1.0) / shape


def _unit(h):
    """uint64 hash -> uniform in (0, 1]."""
    if isinstance(h, np.ndarray):
        return ((h >> np.uint64(11)).astype(np.float64) + 1.0) / float(1 << 53)
    return ((h >> 11) + 1.0) / float(1 << 53)


class Dataset:
    """The keyspace of one configuration under one seed."""

    def __init__(self, config: dict, seed: int) -> None:
        self.config = config
        self.seed = int(seed)
        self.n = int(config.get("recordcount") or config["keycount"])
        self._seed_mix = mix64(self.seed & _M64)
        rng = np.random.default_rng([self.seed & _M64, 1])
        self.values = config["values"]
        self.max_value = int(self.values.get("max_bytes", 1 << 20))
        self.pool = _ALNUM[
            rng.integers(0, len(_ALNUM), POOL_BYTES + self.max_value,
                         dtype=np.uint8)
        ]
        self.keys = self._make_keys()

    # -- keys -----------------------------------------------------------------
    def _make_keys(self) -> list[bytes]:
        kc = self.config["keys"]
        idx = np.arange(self.n)
        if kc["kind"] == "prefix":
            prefix = kc["prefix"].encode()
            return [prefix + b"%d" % i for i in idx.tolist()]
        if kc["kind"] == "gev_digits":
            u = _unit(mix64(idx.astype(np.uint64) ^ np.uint64(_SIZE_SEED)))
            sizes = np.rint(gev_quantile(u, *kc["gev"])).astype(np.int64)
            digits = np.char.str_len(idx.astype(str))
            sizes = np.clip(np.maximum(sizes, digits), 1, kc["max_bytes"])
            return [str(i).zfill(int(s)).encode()
                    for i, s in zip(idx.tolist(), sizes.tolist())]
        raise ValueError(f"unknown key kind {kc['kind']!r}")

    # -- values ---------------------------------------------------------------
    def _size(self, i, ver):
        vc = self.values
        if vc["kind"] == "fixed":
            if isinstance(i, np.ndarray):
                return np.full(i.shape, vc["bytes"], np.int64)
            return vc["bytes"]
        if vc["kind"] == "gpareto":
            # Loaded sizes (ver 0) come from the fixed stream; later writes
            # draw a fresh size, as mutilate draws one per SET.
            if isinstance(i, np.ndarray):
                salt = np.where(ver == 0, np.uint64(_SIZE_SEED),
                                np.uint64(self._seed_mix))
                h = mix64((i.astype(np.uint64) << np.uint64(24))
                          ^ ver.astype(np.uint64) ^ salt)
                s = np.rint(gpareto_quantile(_unit(h), *vc["gpareto"]))
                return np.clip(s, 1, self.max_value).astype(np.int64)
            salt = _SIZE_SEED if ver == 0 else self._seed_mix
            h = mix64(((int(i) << 24) ^ int(ver) ^ salt) & _M64)
            s = round(float(gpareto_quantile(_unit(h), *vc["gpareto"])))
            return int(min(max(s, 1), self.max_value))
        raise ValueError(f"unknown value kind {vc['kind']!r}")

    def _offset(self, i, ver):
        if isinstance(i, np.ndarray):
            h = mix64((i.astype(np.uint64) * np.uint64(0x100000001B3))
                      ^ (ver.astype(np.uint64) << np.uint64(40))
                      ^ np.uint64(self._seed_mix))
            return (h % np.uint64(POOL_BYTES)).astype(np.int64)
        h = mix64(((int(i) * 0x100000001B3) ^ (int(ver) << 40)
                   ^ self._seed_mix) & _M64)
        return h % POOL_BYTES

    def value(self, i: int, ver: int) -> bytes:
        off = self._offset(i, ver)
        return self.pool[off: off + self._size(i, ver)].tobytes()

    def values_at(self, idx: np.ndarray, ver: np.ndarray) -> list[bytes]:
        """``value`` over arrays, vectorised where it pays."""
        offs = self._offset(idx, ver).tolist()
        sizes = self._size(idx, ver).tolist()
        pool = self.pool
        return [pool[o: o + s].tobytes() for o, s in zip(offs, sizes)]


# ------------------------------------------------------------------ traffic

def conn_of_key(idx: np.ndarray, connections: int) -> np.ndarray:
    """The connection that carries every request on a key: one key's
    requests stay in order on one connection, so each GET has exactly one
    right answer."""
    return (mix64(idx.astype(np.uint64) ^ np.uint64(0xC0FFEE))
            % np.uint64(connections)).astype(np.int64)


def pick_keys(rng: np.random.Generator, count: int, n: int,
              distribution: str) -> np.ndarray:
    if distribution == "zipfian":
        return scrambled_zipfian(rng, count, n)
    if distribution == "uniform":
        return rng.integers(0, n, count, dtype=np.int64)
    raise ValueError(f"unknown request distribution {distribution!r}")


class Schedule:
    """Open-loop requests: due time (s from the window's start), kind
    (0 GET, 1 SET), key index, and version (written, or expected back)."""

    def __init__(self, due, kind, key, ver) -> None:
        self.due, self.kind, self.key, self.ver = due, kind, key, ver

    def __len__(self) -> int:
        return len(self.due)


def open_loop(n: int, traffic: dict, seed: int, stream: int, t_from: float,
              t_to: float, rate: float, versions: np.ndarray) -> Schedule:
    """Poisson arrivals at ``rate`` over [t_from, t_to), each a GET with
    probability ``read_proportion`` and otherwise a SET of a new version.
    ``versions`` (latest written version per key) is advanced in place."""
    rng = np.random.default_rng([seed & _M64, 100 + stream])
    span = t_to - t_from
    expect = rate * span
    gaps = rng.exponential(1.0 / rate, int(expect + 8 * math.sqrt(expect) + 64))
    due = t_from + np.cumsum(gaps)
    due = due[due < t_to]
    m = len(due)
    kind = (rng.random(m) >= traffic["read_proportion"]).astype(np.int8)
    key = pick_keys(rng, m, n, traffic["requestdistribution"])
    ver = np.empty(m, np.int64)
    # Versions: the j-th SET of a key in schedule order writes
    # versions[key] + j; a GET expects the latest SET before it.
    order = np.lexsort((np.arange(m), key))
    k_s, upd_s = key[order], kind[order] == 1
    start = np.r_[True, k_s[1:] != k_s[:-1]]
    grp = np.cumsum(start) - 1
    upd_cum = np.cumsum(upd_s)
    base_cum = np.r_[0, upd_cum][np.flatnonzero(start)]
    occ = upd_cum - base_cum[grp]  # SETs so far in this key's group
    ver[order] = versions[k_s] + occ
    if m:
        last = np.r_[np.flatnonzero(start)[1:] - 1, m - 1]
        versions[k_s[last]] += occ[last]
    return Schedule(due, kind, key, ver)
