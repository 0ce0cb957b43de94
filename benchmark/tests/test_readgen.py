"""The read generator's check: a GET may return any version from the last
change certain before it was sent to the last possible by its answer."""

import numpy as np

from benchmark import readgen


class _Values:
    def value(self, i, v):
        return b"%d-%d" % (i, v)

    def values_at(self, idx, ver):
        return [self.value(i, v) for i, v in zip(idx.tolist(), ver.tolist())]


# Key 3 on replica a: version 1 possible from 10 s, certain from 11 s;
# version 2 possible from 20 s, certain from 21 s. Replica b: no change.
TIMELINE = {"key": np.array([3, 3]), "ver": np.array([1, 2]),
            "possible": np.array([1010.0, 1020.0]),
            "certain": np.array([1011.0, 1021.0])}
NONE = {"key": np.zeros(0, np.int64), "ver": np.zeros(0, np.int64),
        "possible": np.zeros(0), "certain": np.zeros(0)}
# (key, sent, answered, version read, allowed on a, allowed on b)
CASES = [(3, 1005, 1006, 0, True, True), (3, 1005, 1006, 1, False, False),
         (3, 1010.5, 1010.6, 0, True, True),
         (3, 1010.5, 1010.6, 1, True, False),
         (3, 1012, 1013, 0, False, True), (3, 1012, 1013, 1, True, False),
         (3, 1012, 1020.5, 2, True, False), (3, 1022, 1023, 1, False, False),
         (3, 1022, 1023, 2, True, False), (5, 1022, 1023, 0, True, True)]


def test_each_read_is_held_to_its_window():
    ds = _Values()
    rec = {"key": np.array([c[0] for c in CASES]),
           "sent": np.array([c[1] for c in CASES], float),
           "done": np.array([c[2] for c in CASES], float),
           "hash": np.array([hash(b"VALUE " + ds.value(c[0], c[3]))
                             for c in CASES], np.int64),
           "due": np.zeros(len(CASES)), "conn": np.zeros(len(CASES), int)}
    base = np.zeros(10, np.int64)
    for x, col in ((0, 4), (1, 5)):
        node = np.full(len(CASES), x, np.int8)
        out = readgen._check(ds, rec, node, [TIMELINE, NONE], base, 1000.0)
        assert out["wrong"].tolist() == [not c[col] for c in CASES]
