"""YCSB D's generators and reference: the latest generator draws what
YCSB's closed form says, the schedule never reads a key before its
insert, and the growing reference chains over the keys present."""

import numpy as np
import pytest

from benchmark import latest, reference
from benchmark.data import conn_of_key
from benchmark.reference_growing import GrowingModel

YCSB_D = {"recordcount": 5000, "keys": {"kind": "prefix", "prefix": "user"},
          "values": {"kind": "fixed", "bytes": 1000}}
TRAFFIC = {"insert_proportion": 0.05}


@pytest.mark.parametrize("newest,items0", [
    (262_143, 262_143),  # before any insert
    (303_000, 262_143),  # ~41K inserts later: zeta grown, eta as built
    (4_095, 4_095),
])
def test_latest_quantiles_follow_ycsb_closed_form(newest, items0):
    zeta = latest.zeta_prefix(newest)
    draws = 400_000
    keys = latest.skewed_latest(np.random.default_rng(3),
                                np.full(draws, newest), items0, zeta)
    assert keys.min() >= 0 and keys.max() <= newest
    rank = newest - keys
    r = np.array([0, 1, 2, 3, 10, 100, 1000, newest // 10, newest // 2])
    want = latest.latest_rank_cdf(r, newest, items0, zeta)
    got = (rank[None, :] <= r[:, None]).mean(axis=1)
    # Five standard errors of a binomial share.
    tol = 5 * np.sqrt(want * (1 - want) / draws) + 1e-9
    assert np.all(np.abs(got - want) <= tol), (got, want)
    # The newest record is the hottest: 1 / zetan of all draws.
    assert want[0] == pytest.approx(1 / zeta[newest])


@pytest.mark.parametrize("seed", [5, 2**31 + 11, 3_000_000_017])
def test_schedule_repeats_and_never_reads_before_the_insert(seed):
    n = 5000
    a = latest.read_latest(n, TRAFFIC, seed, 0, -1.0, 4.0, 6000)
    b = latest.read_latest(n, TRAFFIC, seed, 0, -1.0, 4.0, 6000)
    assert all(np.array_equal(x, y) for x, y in
               zip((a.due, a.kind, a.key, a.ver), (b.due, b.kind, b.key,
                                                   b.ver)))
    ins = a.kind == 1
    assert 0.04 < ins.mean() < 0.06
    # Inserts take the next keys in due order, each once, at version 1.
    assert a.key[ins].tolist() == list(range(n, n + int(ins.sum())))
    assert np.all(a.ver[ins] == 1)
    # Every read of an inserted key comes after its insert, on the same
    # connection, and expects the inserted version.
    due_of = dict(zip(a.key[ins].tolist(), a.due[ins].tolist()))
    conn = conn_of_key(a.key, 16)
    conn_of = dict(zip(a.key[ins].tolist(), conn[ins].tolist()))
    for j in np.flatnonzero(~ins & (a.key >= n)).tolist():
        k = int(a.key[j])
        assert due_of[k] < a.due[j] and conn_of[k] == conn[j]
        assert a.ver[j] == 1
    assert np.all(a.ver[~ins & (a.key < n)] == 0)
    # Reads lean to the newest records.
    newest = n - 1 + np.cumsum(a.kind)[~ins]
    assert np.median(newest - a.key[~ins]) < 100


def test_growing_dataset_continues_the_loaded_keys():
    ds = latest.GrowingDataset(YCSB_D, 7, 30)
    assert ds.n == 5000 and len(ds.keys) == 5030
    assert ds.keys[5000] == b"user5000" and ds.keys[4999] == b"user4999"
    # A load worker rebuilds the same keys and values from ``config``.
    again = latest.Dataset(ds.config, 7)
    assert again.keys == ds.keys
    assert again.value(5010, 1) == ds.value(5010, 1)


def test_growing_reference_chains_over_the_keys_present():
    ds = latest.GrowingDataset({**YCSB_D, "recordcount": 40,
                                "values": {"kind": "fixed", "bytes": 30}},
                               9, 5)
    m = GrowingModel(ds)
    loaded = [(ds.keys[i], ds.value(i, 0)) for i in range(40)]
    assert m.root_hex() == reference.root_hex(loaded)
    np.maximum.at(m.versions, np.array([41, 43]), 1)  # two acked inserts
    m.extra[b"bench_probe"] = b"7"
    want = loaded + [(ds.keys[41], ds.value(41, 1)),
                     (ds.keys[43], ds.value(43, 1)), (b"bench_probe", b"7")]
    assert m.root_hex() == reference.root_hex(want)
    with pytest.raises(KeyError):
        m.values_of(np.array([42]))  # never inserted
