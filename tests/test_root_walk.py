"""The promotion-chain root walk (``mkv_root_walk``) against the golden tree.

The walk reads one padded node per level and hashes a chain of log2(C)
node pairs. Its root must equal ``merkle/cpu.py``'s odd-promotion root over
the first n leaves, through both branches of the node-hash dispatch:

- the scan formulation (every backend but TPU);
- the Pallas node kernel's branch. The Pallas interpreter cannot run the
  unrolled 128-round kernel on the CPU in a test's time, so the kernel's
  own body (``_node_kernel``), its wrapper's padding and word-plane layout
  run as they are, with only ``pallas_call`` emulated on the host. The
  compiled kernel is checked on the chip (tests/test_dispatch.py) and
  compiled for a described v5e in tests/test_tpu_compile.py.
"""

import hashlib
import types
from functools import lru_cache

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import merklekv_tpu.ops.sha256_pallas as sp
from merklekv_tpu.merkle import incremental
from merklekv_tpu.merkle.cpu import build_levels
from merklekv_tpu.ops.sha256 import _IV, digest_to_bytes, digests_to_bytes
from merklekv_tpu.parallel.mesh import make_mesh

MAX_C = 2048
_LEAVES = np.random.default_rng(25).integers(
    0, 1 << 32, (MAX_C, 8), dtype=np.uint32
)


def _cases() -> list[tuple[int, int]]:
    """(live count n, capacity C): C = next power of two of n, as the
    state sizes it, plus random n in wider capacities (a sharded tree keeps
    C at least the shard count)."""
    ns = set(range(1, 41))
    for k in range(1, 11):
        ns |= {(1 << k) - 1, 1 << k, (1 << k) + 1}
    cases = [(n, incremental._next_pow2(n)) for n in sorted(ns)]
    rng = np.random.default_rng(2025)
    for c in (64, 128, 256, 512, 1024, 2048):
        cases.append((int(rng.integers(1, c + 1)), c))
    return cases


CASES = _cases()


def _padded_levels(n: int, c: int) -> list[np.ndarray]:
    """The padded tree over the first n leaves, zero sentinels after them,
    reduced with hashlib."""
    cur = np.zeros((c, 8), np.uint32)
    cur[:n] = _LEAVES[:n]
    levels = [cur]
    while len(cur) > 1:
        digs = digests_to_bytes(cur)
        cur = np.stack([
            np.frombuffer(hashlib.sha256(a + b).digest(), ">u4")
            for a, b in zip(digs[0::2], digs[1::2])
        ]).astype(np.uint32)
        levels.append(cur)
    return levels


def _np_iv_tiles(shape):
    return [np.full(shape, v, np.uint32) for v in _IV]


def _host_pallas_call(kernel, *, grid, out_shape, **_):
    """``pallas_call`` on the host: the kernel body runs on numpy blocks,
    block i of dim 0 at grid step i (the node kernel's index map). The
    body runs as a copy whose IV tiles are numpy: callbacks of several
    devices run at once, so nothing of the module is patched."""
    host_kernel = types.FunctionType(
        kernel.__code__, {**kernel.__globals__, "_iv_tiles": _np_iv_tiles}
    )

    def call(*args):
        def host(*arrays):
            out = np.zeros(out_shape.shape, out_shape.dtype)
            for i in range(grid[0]):
                host_kernel(*(a[i : i + 1] for a in arrays), out[i : i + 1])
            return out

        return jax.pure_callback(host, out_shape, *args)

    return call


def _level_shardings(c: int, mesh):
    """Each level's placement on ``mesh``: split on the key axis while it
    has rows for every device, replicated above."""
    return tuple(
        NamedSharding(mesh, P("key", None) if (c >> j) >= mesh.size else P())
        for j in range(c.bit_length())
    )


@lru_cache(maxsize=None)
def _walk(c: int, kernel: bool, mesh=None):
    """The walk compiled through one branch, outside the program cache."""
    shardings = (
        _level_shardings(c, mesh) if mesh is not None
        else (None,) * c.bit_length()
    )
    levels = tuple(
        jax.ShapeDtypeStruct((c >> j, 8), jnp.uint32, sharding=sh)
        for j, sh in enumerate(shardings)
    )
    n = jax.ShapeDtypeStruct((), jnp.int32)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MKV_SHA256_BACKEND", "pallas" if kernel else "scan")
        if kernel:
            # One pair per call takes the kernel's branch here too, and the
            # wrapper is traced afresh so no emulated trace outlives this.
            impl = sp._node_pairs_impl.__wrapped__
            mp.setattr(sp, "_MIN_PALLAS_PAIRS_INTERP", 0)
            mp.setattr(sp, "pl", types.SimpleNamespace(
                BlockSpec=sp.pl.BlockSpec, pallas_call=_host_pallas_call))
            mp.setattr(sp, "_node_pairs_impl", jax.jit(
                types.FunctionType(impl.__code__, impl.__globals__),
                static_argnames=("interpret",),
            ))
        fn = incremental._ref_root_fn.__wrapped__(c, kernel, mesh)
        return fn.lower(levels, n).compile()


def _golden(n: int) -> bytes:
    return build_levels(digests_to_bytes(_LEAVES[:n]))[-1][0]


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
@pytest.mark.parametrize("n,c", CASES, ids=[f"n{n}-c{c}" for n, c in CASES])
def test_root_walk_matches_golden_tree(n, c, kernel):
    levels = tuple(jnp.asarray(lv) for lv in _padded_levels(n, c))
    root = _walk(c, kernel)(levels, jnp.int32(n))
    assert digest_to_bytes(np.asarray(root)) == _golden(n)


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
@pytest.mark.parametrize("n", [1, 6, 33, 64])
def test_root_walk_over_sharded_levels(n, kernel):
    """Levels sharded over a 2-device mesh, as a sharded tree holds them;
    on the kernel's branch each hash runs under shard_map."""
    c = 64
    mesh = make_mesh({"key": 2}, devices=jax.devices()[:2])
    levels = tuple(
        jax.device_put(lv, sh)
        for lv, sh in zip(_padded_levels(n, c), _level_shardings(c, mesh))
    )
    root = _walk(c, kernel, mesh)(levels, jnp.int32(n))
    assert digest_to_bytes(np.asarray(root)) == _golden(n)
