"""The repair cell's plan: episodes repeat from the seed, and the leaf-hash
shapes it makes ready in set-up are the shapes the program packs."""

import numpy as np

from benchmark import data
from benchmark.drivers import sync_episodes as se
from merklekv_tpu.merkle.packing import pack_leaf_groups

ETC = {"keycount": 6000,
       "keys": {"kind": "gev_digits", "gev": [30.7984, 8.20449, 0.078688],
                "max_bytes": 250},
       "values": {"kind": "gpareto", "gpareto": [15.0, 214.476, 0.348238],
                  "max_bytes": 1048000}}


def test_episodes_repeat_and_version_every_set():
    ds = data.Dataset(ETC, 2**31 + 7)
    v0 = np.zeros(ds.n, np.int64)
    a = se.plan_episodes(ds, v0, 3, 900, 2**31 + 7)
    b = se.plan_episodes(ds, v0, 3, 900, 2**31 + 7)
    assert not v0.any()
    v = v0.copy()
    for ea, eb in zip(a, b):
        assert np.array_equal(ea["key"], eb["key"])
        assert np.array_equal(ea["ver"], eb["ver"])
        # The j-th SET of a key in an episode writes its version + j.
        for k, ver in zip(ea["key"].tolist(), ea["ver"].tolist()):
            assert ver == v[k] + 1
            v[k] = ver
        assert np.array_equal(ea["distinct"], np.unique(ea["key"]))


def test_shapes_are_the_ones_the_program_packs():
    import merklekv_tpu.merkle.packing as packing

    ds = data.Dataset(ETC, 5)
    idx = np.arange(ds.n)
    v0 = np.zeros(ds.n, np.int64)
    plan = se.plan_episodes(ds, v0, 2, 2500, 11)
    for group in (1 << 20, 1 << 9):
        old = packing.GROUP_BLOCKS
        packing.GROUP_BLOCKS = group
        try:
            whole, batch = se.reachable_shapes(ds, v0, plan)
            v = v0.copy()
            want_whole, parts = set(), set()
            rng = np.random.default_rng(1)
            for ep in plan:
                vals = ds.values_at(idx, v)
                want_whole |= {(p.n, p.max_blocks) for _, p
                               in pack_leaf_groups(ds.keys, vals)}
                # Pump batches and repair pages: any subset of the SETs.
                for m in (1, 17, 300, 2500):
                    sub = rng.choice(len(ep["key"]), m, replace=False)
                    k, ver = ep["key"][sub], ep["ver"][sub]
                    parts |= {(p.n, p.max_blocks) for _, p in
                              pack_leaf_groups([ds.keys[i] for i in
                                                k.tolist()],
                                               ds.values_at(k, ver))}
                np.maximum.at(v, ep["key"], ep["ver"])
        finally:
            packing.GROUP_BLOCKS = old
        assert whole == want_whole
        assert parts <= batch


def test_warm_shapes_fill_their_width():
    from merklekv_tpu.merkle.packing import pack_leaves

    for w in (1, 2, 8, 1024):
        value = b"x" * (64 * w - 17 - 1)
        assert pack_leaves([b"w"], [value]).max_blocks == w
