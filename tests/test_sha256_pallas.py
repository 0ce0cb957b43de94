"""Pallas SHA-256 kernels.

The kernel MATH (`_compress_tiles`, plane packing) is golden-tested against
hashlib here on any backend as pure jnp. The compiled kernels themselves
only run on a real TPU — the Pallas interpreter's cost explodes past ~32
unrolled rounds, so kernel-level tests are gated on backend=="tpu" (the
driver's bench also cross-checks the kernel root against the CPU golden core
on every TPU run).
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from merklekv_tpu.merkle.cpu import build_levels
from merklekv_tpu.merkle.encoding import leaf_hash
from merklekv_tpu.merkle.packing import pack_leaves
from merklekv_tpu.ops.sha256 import _IV, digest_to_bytes
from merklekv_tpu.ops.sha256_pallas import (
    TILE_M,
    _compress_tiles,
    _from_planes,
    _iv_tiles,
    _to_planes,
    build_levels_pallas,
    leaf_digests_pallas,
    tree_root_pallas,
)

on_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="compiled pallas kernels need TPU"
)


def _hashlib_rows(msgs):
    return np.stack(
        [np.frombuffer(hashlib.sha256(m).digest(), ">u4").astype(np.uint32)
         for m in msgs]
    )


# ----------------------------------------------------- kernel math (any backend)

def test_compress_tiles_matches_hashlib():
    """One compression on a [8, 128] tile of distinct single-block messages."""
    rng = np.random.RandomState(0)
    n = 8 * 128
    msgs = [rng.bytes(32) for _ in range(n)]
    # Build padded blocks: 32-byte message -> 0x80, bitlen=256.
    words = np.zeros((16, n), np.uint32)
    for i, m in enumerate(msgs):
        w = np.frombuffer(m + b"\x80" + b"\x00" * 23 + (256).to_bytes(8, "big"),
                          ">u4").astype(np.uint32)
        words[:, i] = w
    tiles = [jnp.asarray(words[i].reshape(8, 128)) for i in range(16)]
    state = _compress_tiles(_iv_tiles((8, 128)), tiles)
    got = np.stack([np.asarray(s) for s in state]).reshape(8, n).T
    np.testing.assert_array_equal(got, _hashlib_rows(msgs))


def test_compress_tiles_chaining_two_blocks():
    """Two-block message: compress twice, compare against hashlib."""
    msg = bytes(range(100))  # 100 bytes -> 2 blocks
    padded = msg + b"\x80" + b"\x00" * 19 + (800).to_bytes(8, "big")
    assert len(padded) == 128
    w = np.frombuffer(padded, ">u4").astype(np.uint32)
    shape = (8, 128)
    state = _iv_tiles(shape)
    for b in range(2):
        tiles = [jnp.full(shape, w[b * 16 + i], jnp.uint32) for i in range(16)]
        state = _compress_tiles(state, tiles)
    got = np.stack([np.asarray(s)[0, 0] for s in state])
    expect = np.frombuffer(hashlib.sha256(msg).digest(), ">u4").astype(np.uint32)
    np.testing.assert_array_equal(got, expect)


def test_plane_roundtrip():
    rng = np.random.RandomState(1)
    rows = rng.randint(0, 2**32, (2 * TILE_M, 8), dtype=np.uint64).astype(
        np.uint32
    )
    back = np.asarray(_from_planes(_to_planes(jnp.asarray(rows))))
    np.testing.assert_array_equal(back, rows)


def test_iv_tiles_match_spec():
    tiles = _iv_tiles((8, 128))
    got = np.stack([np.asarray(t)[0, 0] for t in tiles])
    np.testing.assert_array_equal(got, _IV)


def _emulated_leaf_call(state, planes, nb, interpret):
    """The leaf kernel's contract in plain jnp (the rolled-scan
    compression): walk every block of the call, lanes past their valid
    count keep their state."""
    from merklekv_tpu.ops.sha256 import _compress

    def step(j, st):
        words = [planes[:, j, w] for w in range(16)]
        new = jnp.moveaxis(_compress(jnp.moveaxis(st, 1, -1), words), -1, 1)
        return jnp.where((nb > j)[:, None], new, st)

    return jax.lax.fori_loop(0, planes.shape[1], step, state)


@pytest.mark.parametrize("chunk", [512, 3])
def test_leaf_impl_planes_and_chunking_match_hashlib(monkeypatch, chunk):
    """Row padding, word-plane layout, IV seeding and the state carried
    across block chunks (a message longer than LEAF_CHUNK_BLOCKS runs as a
    loop of kernel calls) — checked on any backend with the kernel call
    emulated; the compiled kernel itself is checked on the chip."""
    import merklekv_tpu.ops.sha256_pallas as sp

    monkeypatch.setattr(sp, "_leaf_call", _emulated_leaf_call)
    monkeypatch.setattr(sp, "LEAF_CHUNK_BLOCKS", chunk)
    rng = np.random.RandomState(5)
    keys = [b"ck%02d" % i for i in range(9)]
    values = [rng.bytes(int(rng.randint(0, 700))) for _ in keys]
    packed = pack_leaves(keys, values, min_blocks=13)
    got = np.asarray(
        sp._leaf_digests_impl(packed.blocks, packed.nblocks, interpret=True)
    )
    expect = np.stack(
        [np.frombuffer(leaf_hash(k, v), ">u4").astype(np.uint32)
         for k, v in zip(keys, values)]
    )
    np.testing.assert_array_equal(got, expect)


# ----------------------------------------------------- compiled kernels (TPU)

@on_tpu
def test_leaf_kernel_vs_hashlib_tpu():
    keys = [f"pk{i:04d}".encode() for i in range(300)]
    values = [b"v%d" % (i * 7) for i in range(300)]
    packed = pack_leaves(keys, values)
    got = np.asarray(leaf_digests_pallas(packed.blocks, packed.nblocks))
    expect = np.stack(
        [np.frombuffer(leaf_hash(k, v), ">u4").astype(np.uint32)
         for k, v in zip(keys, values)]
    )
    np.testing.assert_array_equal(got, expect)


@on_tpu
def test_multi_block_masking_tpu():
    keys = [b"k" * (1 + (i % 3)) for i in range(50)]
    values = [b"x" * (i * 17 % 200) for i in range(50)]
    packed = pack_leaves(keys, values)
    assert packed.max_blocks >= 2
    got = np.asarray(leaf_digests_pallas(packed.blocks, packed.nblocks))
    hl = _hashlib_rows(
        [len(k).to_bytes(4, "big") + k + len(v).to_bytes(4, "big") + v
         for k, v in zip(keys, values)]
    )
    np.testing.assert_array_equal(got, hl)


@on_tpu
@pytest.mark.parametrize("n", [1, 2, 97, 3001])
def test_tree_root_matches_cpu_tpu(n):
    items = [(f"tk{i:05d}", f"tv{i}") for i in range(n)]
    packed = pack_leaves([k.encode() for k, _ in items],
                         [v.encode() for _, v in items])
    leaves = leaf_digests_pallas(packed.blocks, packed.nblocks)
    root = np.asarray(tree_root_pallas(leaves))
    expect = build_levels([leaf_hash(k, v) for k, v in items])[-1][0]
    assert digest_to_bytes(root) == expect


@on_tpu
def test_build_levels_matches_scan_path_tpu():
    from merklekv_tpu.merkle.jax_engine import build_levels_device

    rng = np.random.RandomState(11)
    leaves = rng.randint(0, 2**32, (4097, 8), dtype=np.uint64).astype(np.uint32)
    got = build_levels_pallas(leaves)
    expect = build_levels_device(leaves)
    assert len(got) == len(expect)
    for g, e in zip(got, expect):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
