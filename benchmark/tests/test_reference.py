"""The benchmark's hashlib chain is the store's documented tree."""

import numpy as np

from benchmark import reference
from benchmark.data import Dataset


def test_chain_equals_the_cpu_golden_root():
    from merklekv_tpu.merkle.cpu import root_from_leaf_hashes

    for n in (1, 2, 3, 7, 64, 1001):
        items = [(b"k%05d" % i, b"v" * (i % 90)) for i in range(n)]
        leaves = [reference.leaf(k, v) for k, v in sorted(items)]
        assert reference.root_hex(items) == \
            root_from_leaf_hashes(leaves).hex()


def test_model_tracks_versions_and_explicit_values():
    ds = Dataset({"recordcount": 10, "keys": {"kind": "prefix",
                                              "prefix": "user"},
                  "values": {"kind": "fixed", "bytes": 20}}, 1)
    m = reference.Model(ds)
    m.explicit[3] = (0, b"explicit")
    m.versions[4] = 2
    vals = m.values_of(np.arange(10))
    assert vals[3] == b"explicit" and vals[4] == ds.value(4, 2)
    m.versions[3] = 1  # a later write replaces the explicit value
    assert m.values_of(np.array([3]))[0] == ds.value(3, 1)
    m.extra[b"probe"] = b"1"
    assert (b"probe", b"1") in m.items()
