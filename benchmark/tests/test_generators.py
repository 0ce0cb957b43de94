"""The generators repeat from a seed and draw what their sources say."""

import numpy as np

from benchmark import data

YCSB = {"recordcount": 5000, "keys": {"kind": "prefix", "prefix": "user"},
        "values": {"kind": "fixed", "bytes": 1000}}
ETC = {"keycount": 5000,
       "keys": {"kind": "gev_digits", "gev": [30.7984, 8.20449, 0.078688],
                "max_bytes": 250},
       "values": {"kind": "gpareto", "gpareto": [15.0, 214.476, 0.348238],
                  "max_bytes": 1040000}}


def _fnv_java(v: int) -> int:
    """YCSB Utils.fnvhash64, as Java computes it on a signed long."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        h = (h * 1099511628211) & ((1 << 64) - 1)
        v >>= 8
    s = h - (1 << 64) if h >= 1 << 63 else h
    return abs(s)


def test_fnv_matches_ycsb():
    vals = np.array([0, 1, 2, 12345, 9_999_999_999], np.int64)
    got = data.fnv1a64_long(vals).tolist()
    assert got == [_fnv_java(int(v)) for v in vals]


def test_scrambled_zipfian_repeats_and_is_skewed():
    a = data.scrambled_zipfian(np.random.default_rng(7), 200_000, 262_144)
    b = data.scrambled_zipfian(np.random.default_rng(7), 200_000, 262_144)
    assert np.array_equal(a, b)
    assert a.min() >= 0 and a.max() < 262_144
    counts = np.bincount(a)
    # Zipf 0.99 over 10^10 ranks: the top two ranks take ~3.8% and ~1.9%.
    top = np.sort(counts)[::-1]
    assert 0.03 < top[0] / len(a) < 0.05
    # Rank 0 lands where FNV sends it, whatever the seed.
    assert np.argmax(counts) == _fnv_java(0) % 262_144


def test_etc_quantiles_follow_mutilate():
    u = np.array([0.5])
    assert np.allclose(data.gpareto_quantile(u, 15.0, 214.476, 0.348238),
                       15.0 + 214.476 * (0.5 ** -0.348238 - 1) / 0.348238)
    assert np.allclose(data.gev_quantile(u, 30.7984, 8.20449, 0.078688),
                       30.7984 + 8.20449 * ((-np.log(0.5)) ** -0.078688 - 1)
                       / 0.078688)


def test_datasets_repeat_from_seed_and_keep_sizes_across_seeds():
    for cfg in (YCSB, ETC):
        a, b, c = (data.Dataset(cfg, s) for s in (11, 11, 2**31 + 5))
        assert a.keys == b.keys == c.keys
        idx = np.arange(a.n)
        v0 = np.zeros(a.n, np.int64)
        va, vb, vc = (d.values_at(idx, v0) for d in (a, b, c))
        assert va == vb
        assert va != vc
        assert [len(x) for x in va] == [len(x) for x in vc]
        assert a.value(17, 3) == a.values_at(np.array([17]),
                                             np.array([3]))[0]
        assert a.value(17, 3) != a.value(17, 4)


def test_etc_sizes_have_the_published_shape():
    ds = data.Dataset(dict(ETC, keycount=50_000), 3)
    klen = np.array([len(k) for k in ds.keys])
    assert 25 < np.median(klen) < 40 and klen.max() <= 250
    sizes = np.array([len(v) for v in ds.values_at(
        np.arange(ds.n), np.zeros(ds.n, np.int64))])
    # GPareto(15, 214.476, 0.348): mean 15 + 214.476 / (1 - 0.348) = 344.
    assert 300 < sizes.mean() < 390 and sizes.min() >= 15


def test_open_loop_versions_follow_schedule_order():
    versions = np.zeros(50, np.int64)
    tr = {"read_proportion": 0.5, "requestdistribution": "uniform"}
    s = data.open_loop(50, tr, 5, 0, -1.0, 2.0, 400.0, versions)
    again = data.open_loop(50, tr, 5, 0, -1.0, 2.0, 400.0,
                           np.zeros(50, np.int64))
    assert np.array_equal(s.key, again.key) and np.array_equal(s.ver,
                                                                again.ver)
    assert np.all(np.diff(s.due) > 0) and s.due[0] >= -1 and s.due[-1] < 2
    last = np.zeros(50, np.int64)
    for kind, k, v in zip(s.kind, s.key, s.ver):
        if kind == 1:
            assert v == last[k] + 1
            last[k] = v
        else:
            assert v == last[k]
    assert np.array_equal(last, versions)


def _gev_closed_form(p, loc, scale, shape):
    """GEV quantile from its CDF exp(-(1 + shape (x - loc) / scale)^(-1 /
    shape))."""
    return loc + scale * ((-np.log(p)) ** (-shape) - 1.0) / shape


def _gpareto_closed_form(p, loc, scale, shape):
    """Generalized Pareto quantile from its CDF 1 - (1 + shape (x - loc) /
    scale)^(-1 / shape)."""
    return loc + scale * ((1.0 - p) ** (-shape) - 1.0) / shape


def test_etc_sample_quantiles_match_the_closed_forms():
    """The sizes a dataset draws (keys once, values at every write) follow
    the published GEV and generalized Pareto at their quantiles."""
    cfg = dict(ETC, keycount=200_000,
               values=dict(ETC["values"], max_bytes=1_048_000))
    ds = data.Dataset(cfg, 2**31 + 99)
    probs = np.array([0.05, 0.25, 0.5, 0.75, 0.95, 0.99])
    klen = np.array([len(k) for k in ds.keys], np.float64)
    want_k = _gev_closed_form(probs, 30.7984, 8.20449, 0.078688)
    got_k = np.quantile(klen, probs)
    # Sizes are whole bytes: rounding moves a quantile by half a byte.
    assert np.all(np.abs(got_k - want_k) < 1.0), (got_k, want_k)
    idx = np.arange(ds.n)
    for ver in (0, 1, 7):
        sizes = ds._size(idx, np.full(ds.n, ver, np.int64))
        want_v = _gpareto_closed_form(probs, 15.0, 214.476, 0.348238)
        got_v = np.quantile(sizes.astype(np.float64), probs)
        assert np.all(np.abs(got_v - want_v) <= 0.02 * want_v + 1.0), \
            (ver, got_v, want_v)
