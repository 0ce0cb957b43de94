"""Golden tests: JAX SHA-256 vs hashlib, over the packing pipeline."""

import hashlib

import numpy as np
import pytest

from merklekv_tpu.merkle.packing import pack_leaves
from merklekv_tpu.ops.sha256 import (
    digests_to_bytes,
    sha256_blocks,
    sha256_node_pairs,
    sha256_single_block,
)
from merklekv_tpu.merkle.encoding import encode_leaf, leaf_hash, node_hash


def _ref_digest(msg: bytes) -> bytes:
    return hashlib.sha256(msg).digest()


def _pack_one(msg: bytes):
    """Pad an arbitrary message into SHA-256 blocks (test-local helper)."""
    mlen = len(msg)
    nb = (mlen + 9 + 63) // 64
    buf = np.zeros(nb * 64, np.uint8)
    buf[:mlen] = np.frombuffer(msg, np.uint8)
    buf[mlen] = 0x80
    buf[-8:] = np.frombuffer(np.array([mlen * 8], ">u8").tobytes(), np.uint8)
    return buf.view(">u4").astype(np.uint32).reshape(1, nb, 16), np.array(
        [nb], np.int32
    )


@pytest.mark.parametrize(
    "msg",
    [
        b"",
        b"abc",
        b"a" * 55,  # max single-block payload
        b"a" * 56,  # first length that spills to two blocks
        b"a" * 63,
        b"a" * 64,
        b"a" * 119,
        b"a" * 120,
        b"hello world" * 30,  # 330 bytes, 6 blocks
        bytes(range(256)),
    ],
)
def test_sha256_blocks_matches_hashlib(msg):
    blocks, nb = _pack_one(msg)
    got = digests_to_bytes(sha256_blocks(blocks, nb))[0]
    assert got == _ref_digest(msg)


def test_sha256_single_block():
    msg = b"abc"
    blocks, _ = _pack_one(msg)
    got = digests_to_bytes(sha256_single_block(blocks[:, 0, :]))[0]
    assert got == _ref_digest(msg)


def test_mixed_length_batch():
    rng = np.random.default_rng(7)
    msgs = [rng.bytes(int(n)) for n in rng.integers(0, 200, size=64)]
    max_b = max((len(m) + 9 + 63) // 64 for m in msgs)
    blocks = np.zeros((len(msgs), max_b, 16), np.uint32)
    nbs = np.zeros(len(msgs), np.int32)
    for i, m in enumerate(msgs):
        b, nb = _pack_one(m)
        blocks[i, : b.shape[1]] = b[0]
        nbs[i] = nb[0]
    got = digests_to_bytes(sha256_blocks(blocks, nbs))
    for g, m in zip(got, msgs):
        assert g == _ref_digest(m)


def test_node_pairs_matches_cpu_spec():
    rng = np.random.default_rng(3)
    lefts = [rng.bytes(32) for _ in range(17)]
    rights = [rng.bytes(32) for _ in range(17)]
    l = np.stack([np.frombuffer(b, ">u4").astype(np.uint32) for b in lefts])
    r = np.stack([np.frombuffer(b, ">u4").astype(np.uint32) for b in rights])
    got = digests_to_bytes(sha256_node_pairs(l, r))
    for g, lb, rb in zip(got, lefts, rights):
        assert g == node_hash(lb, rb)


def test_pack_leaves_matches_encode_leaf():
    rng = np.random.default_rng(11)
    keys, values = [], []
    for n in range(40):
        keys.append(rng.bytes(int(rng.integers(0, 80))))
        values.append(rng.bytes(int(rng.integers(0, 150))))
    keys += [b"", "héllo\x00".encode(), b"k"]
    values += [b"", b"v", "é世界".encode()]
    packed = pack_leaves(keys, values)
    got = digests_to_bytes(sha256_blocks(packed.blocks, packed.nblocks))
    for g, k, v in zip(got, keys, values):
        assert g == _ref_digest(encode_leaf(k, v))
        assert g == leaf_hash(k, v)


def test_pack_leaves_empty():
    packed = pack_leaves([], [])
    assert packed.n == 0


def test_pack_leaf_groups_bucket_by_pow2_width(monkeypatch):
    """Whole-keyspace packing groups leaves by power-of-two block count:
    one large value never pads the small rows, every leaf lands in exactly
    one group, and each group stays within GROUP_BLOCKS."""
    from merklekv_tpu.merkle import packing

    monkeypatch.setattr(packing, "GROUP_BLOCKS", 64)
    keys = [b"user%d" % i for i in range(40)] + [b"big4k", b"big64k"]
    values = [b"x" * 1010] * 40 + [b"y" * 4096, b"z" * 65536]
    seen = []
    for rows, packed in packing.pack_leaf_groups(keys, values):
        width = packed.max_blocks
        assert width & (width - 1) == 0
        n = packed.n
        assert n & (n - 1) == 0 and n >= len(rows)
        assert n * width <= 64 or len(rows) == 1
        assert list(rows) == sorted(rows)
        got = digests_to_bytes(sha256_blocks(packed.blocks, packed.nblocks))
        for r, g in zip(rows, got):
            assert g == leaf_hash(keys[r], values[r])
        seen += list(rows)
    assert sorted(seen) == list(range(len(keys)))
    widths = {
        packed.max_blocks for _, packed in packing.pack_leaf_groups(
            keys[:40], values[:40])
    }
    assert widths == {32}  # ~1 KB records: 17 blocks -> the 32 bucket


def test_leaf_digests_mixed_sizes_in_input_order():
    from merklekv_tpu.merkle.jax_engine import leaf_digests

    keys = [b"k%03d" % i for i in range(50)]
    values = [b"v" * (i * 97 % 3000) for i in range(50)]
    got = digests_to_bytes(leaf_digests(keys, values))
    assert got == [leaf_hash(k, v) for k, v in zip(keys, values)]
    assert leaf_digests([], []).shape == (0, 8)
