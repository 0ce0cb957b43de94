"""Pallas TPU kernels for batched SHA-256.

The XLA formulation (merklekv_tpu/ops/sha256.py) rolls the 64 rounds in a
``lax.scan``, which materializes the [N, 8] carry in HBM every round —
~128 HBM round-trips per block. These kernels keep the whole compression in
VMEM/vector registers: one HBM read of the message block, one HBM write of
the digest, all 64 rounds unrolled on the VPU.

Layout: word-planes. Messages live on the (sublane, lane) grid — a tile of
``TILE_S x TILE_L`` messages per grid step — and each of the 16 message
words (and 8 state words) is its own [TILE_S, TILE_L] uint32 tile, so every
VPU op uses full tiles. Host-visible tensors stay row-major ([N, B, 16]
blocks, [N, 8] digests); plane packing is jnp reshapes/transposes under jit
that XLA fuses into the surrounding program.

Kernels:
- ``leaf_digests_pallas``: variable-block-count messages with per-message
  valid-block masking (same contract as ``sha256_blocks``). The block axis
  is a second, sequential grid dimension with the state in VMEM scratch,
  so VMEM use and compile time do not grow with message length.
- ``node_pairs_pallas``: Merkle inner nodes — two-digest message, second
  compression on the constant padding block.
- ``tree_root_pallas``: bottom-up tree build; Pallas for the wide levels,
  the scan path for narrow tops where padding would dominate.

Golden tests compare every path against hashlib on the CPU interpreter
(``interpret=True``); on non-TPU backends the wrappers auto-interpret.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from merklekv_tpu.obs.metrics import get_metrics
from merklekv_tpu.ops.sha256 import _IV, _K, _NODE_PAD_BLOCK, sha256_node_pairs

__all__ = [
    "leaf_digests_pallas",
    "node_pairs_pallas",
    "node_level_pallas",
    "tree_root_pallas",
    "pallas_supported",
]

# Tile height 16 = two native (8, 128) uint32 registers per op: the two
# register halves are independent dependency chains, so the VPU's ALUs can
# overlap them — measured ~8% faster than TILE_S=8 on v5e (TILE_S=32
# regresses ~3x: VMEM pressure forces spills).
TILE_S = 16
TILE_L = 128
TILE_M = TILE_S * TILE_L  # messages per grid step

# On real TPU, use the Pallas node kernel for EVERY level: the scan path
# at narrow levels emits ~64 sequential tiny ops per level and costs ~2.5 ms
# of a 15 ms 1M-leaf tree on v5e; a single padded Pallas tile per narrow
# level is far cheaper. Under the interpreter the padded lanes are real
# numpy work, so narrow levels keep the compiled scan path there.
_MIN_PALLAS_PAIRS = 1
_MIN_PALLAS_PAIRS_INTERP = 2048


def pallas_supported() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(interpret) -> bool:
    out = not pallas_supported() if interpret is None else bool(interpret)
    if out:
        # Counted where a kernel is traced: on a chip this must stay 0.
        get_metrics().inc("device.pallas_interpreted")
    return out


# ------------------------------------------------------------ kernel math

def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _compress_tiles(state: list, words: list) -> list:
    """One SHA-256 compression, fully unrolled on [S, L] uint32 tiles.

    state: 8 tiles; words: 16 tiles. Returns the 8 updated state tiles.

    The message schedule is interleaved with the rounds as a rolling
    16-entry window, so only 16 + 8 tiles are live at any point — keeps
    register/VMEM pressure bounded (and the Pallas interpreter tractable)
    instead of materializing all 64 schedule words.
    """
    w = list(words)  # rolling window: w[t % 16] holds the newest 16 words
    a, b, c, d, e, f, g, h = state
    for t in range(64):
        if t < 16:
            wt = w[t]
        else:
            wm15, wm7, wm2, wm16 = w[(t - 15) % 16], w[(t - 7) % 16], w[(t - 2) % 16], w[t % 16]
            s0 = _rotr(wm15, 7) ^ _rotr(wm15, 18) ^ (wm15 >> np.uint32(3))
            s1 = _rotr(wm2, 17) ^ _rotr(wm2, 19) ^ (wm2 >> np.uint32(10))
            wt = wm16 + s0 + wm7 + s1
            w[t % 16] = wt
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + np.uint32(_K[t]) + wt
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + t2
    out = [a, b, c, d, e, f, g, h]
    return [s + o for s, o in zip(state, out)]


def _iv_tiles(shape):
    return [jnp.full(shape, np.uint32(_IV[i]), jnp.uint32) for i in range(8)]


def _const_kw(block16) -> list[int]:
    """K[t] + W[t] (mod 2^32) for all 64 rounds of a CONSTANT message block.

    The message schedule of a known block is compile-time data: expanding it
    here and folding it into the round constant removes the 48-round
    schedule recurrence (~1000 VPU ops) plus one add per round from the
    kernel — the node kernel's second compression is always over the fixed
    padding block, i.e. half its rounds get this for free.
    """
    mask = 0xFFFFFFFF

    def rotr(x, n):
        return ((x >> n) | (x << (32 - n))) & mask

    w = [int(x) & mask for x in block16]
    sched = list(w)
    for t in range(16, 64):
        wm15, wm7, wm2, wm16 = sched[t - 15], sched[t - 7], sched[t - 2], sched[t - 16]
        s0 = rotr(wm15, 7) ^ rotr(wm15, 18) ^ (wm15 >> 3)
        s1 = rotr(wm2, 17) ^ rotr(wm2, 19) ^ (wm2 >> 10)
        sched.append((wm16 + s0 + wm7 + s1) & mask)
    return [(int(_K[t]) + sched[t]) & mask for t in range(64)]


_NODE_PAD_KW = None  # filled lazily (module import order: _NODE_PAD_BLOCK)


def _node_pad_kw() -> list[int]:
    global _NODE_PAD_KW
    if _NODE_PAD_KW is None:
        _NODE_PAD_KW = _const_kw(_NODE_PAD_BLOCK)
    return _NODE_PAD_KW


def _compress_tiles_const(state: list, kw64: list[int]) -> list:
    """One SHA-256 compression over a CONSTANT block whose per-round
    K[t]+W[t] sums were folded at trace time (see _const_kw)."""
    a, b, c, d, e, f, g, h = state
    for t in range(64):
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + np.uint32(kw64[t])
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + t2
    out = [a, b, c, d, e, f, g, h]
    return [s + o for s, o in zip(state, out)]


# ------------------------------------------------------------ leaf kernel

# Blocks one leaf-kernel call walks. A longer message runs as a scan of
# such calls with the state carried between them, so the word-planes one
# call materializes (TILE_M lanes x this many blocks, 64 MiB) stay bounded
# whatever the value size — a lone 1 MiB value still pads to TILE_M lanes.
LEAF_CHUNK_BLOCKS = 512


def _leaf_kernel(state_in_ref, blocks_ref, nblocks_ref, out_ref, state_ref):
    """Grid (tile, block). state_in_ref [1, 8, S, L]; blocks_ref
    [1, 1, 16, S, L] is block j of the tile's messages; nblocks_ref
    [1, S, L] i32 valid blocks per lane; out_ref [1, 8, S, L].

    The 8-word state lives in the VMEM scratch ``state_ref`` [8, S, L]
    across the block axis, so VMEM use and the traced program are one
    compression whatever the message length. Lanes whose message is
    shorter than j (including padding lanes with nblocks == 0) keep their
    state; the digest is written once, at the last block."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        state_ref[...] = state_in_ref[0]

    state = [state_ref[i] for i in range(8)]
    new_state = _compress_tiles(state, [blocks_ref[0, 0, w] for w in range(16)])
    keep = nblocks_ref[0] > j
    for i in range(8):
        state_ref[i] = jnp.where(keep, new_state[i], state[i])

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[0] = state_ref[...]


def _leaf_call(state, planes, nb, interpret):
    """One kernel call: state [G, 8, S, L] through the blocks of planes
    [G, B, 16, S, L], masked by nb [G, S, L] -> [G, 8, S, L]."""
    g, n_blk = planes.shape[0], planes.shape[1]
    tile = lambda i, j: (i, 0, 0, 0)  # noqa: E731
    return pl.pallas_call(
        _leaf_kernel,
        grid=(g, n_blk),
        in_specs=[
            pl.BlockSpec((1, 8, TILE_S, TILE_L), tile,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 16, TILE_S, TILE_L),
                         lambda i, j: (i, j, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, TILE_S, TILE_L), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 8, TILE_S, TILE_L), tile,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((g, 8, TILE_S, TILE_L), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((8, TILE_S, TILE_L), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="sha256_leaf",
    )(state, planes, nb)


def _to_planes(rows: jax.Array) -> jax.Array:
    """[M, W] -> [G, W, S, L] word-planes; M must be G * TILE_M."""
    m, w = rows.shape
    g = m // TILE_M
    # [G, S, L, W] -> [G, W, S, L]
    return rows.reshape(g, TILE_S, TILE_L, w).transpose(0, 3, 1, 2)


def _from_planes(planes: jax.Array) -> jax.Array:
    """[G, W, S, L] -> [G*S*L, W]."""
    g, w = planes.shape[0], planes.shape[1]
    return planes.transpose(0, 2, 3, 1).reshape(g * TILE_M, w)


@partial(jax.jit, static_argnames=("interpret",))
def _leaf_digests_impl(blocks, nblocks, interpret):
    n, n_blk = blocks.shape[0], blocks.shape[1]
    m = ((n + TILE_M - 1) // TILE_M) * TILE_M
    g = m // TILE_M

    def planes_of(rows):  # [n, B, 16] -> [G, B, 16, S, L], rows padded
        b = rows.shape[1]
        rows = jnp.pad(rows.astype(jnp.uint32), ((0, m - n), (0, 0), (0, 0)))
        return rows.reshape(g, TILE_S, TILE_L, b, 16).transpose(0, 3, 4, 1, 2)

    # pad nblocks with 0 so padded lanes keep the IV (never compressed)
    nb = jnp.pad(nblocks.astype(jnp.int32), (0, m - n))
    nb = nb.reshape(g, TILE_S, TILE_L)
    state = jnp.broadcast_to(
        jnp.asarray(_IV, jnp.uint32)[None, :, None, None],
        (g, 8, TILE_S, TILE_L),
    )
    # Chunks of at most LEAF_CHUNK_BLOCKS blocks, the state carried from
    # one kernel call to the next (a single trip for short messages).
    c = min(n_blk, LEAF_CHUNK_BLOCKS)
    k = -(-n_blk // c)
    blocks = jnp.pad(blocks, ((0, 0), (0, k * c - n_blk), (0, 0)))

    def step(i, st):
        chunk = jax.lax.dynamic_slice_in_dim(blocks, i * c, c, axis=1)
        return _leaf_call(st, planes_of(chunk), nb - i * c, interpret)

    out = jax.lax.fori_loop(0, k, step, state)
    return _from_planes(out)[:n]


def leaf_digests_pallas(blocks, nblocks, interpret=None) -> jax.Array:
    """[N, B, 16] u32 padded blocks + [N] i32 valid counts -> [N, 8] digests.

    Drop-in replacement for ``sha256_blocks`` with the rounds in VMEM."""
    if blocks.shape[0] == 0:
        return jnp.zeros((0, 8), jnp.uint32)
    return _leaf_digests_impl(blocks, nblocks, _interpret(interpret))


# ------------------------------------------------------------ node kernel

def _node_kernel(left_ref, right_ref, out_ref):
    """left/right [1, 8, S, L] digest planes -> out [1, 8, S, L]."""
    shape = (left_ref.shape[2], left_ref.shape[3])
    words = [left_ref[0, i] for i in range(8)] + [right_ref[0, i] for i in range(8)]
    state = _compress_tiles(_iv_tiles(shape), words)
    # Second compression is over the fixed 64-byte padding block: its
    # schedule folds away entirely (constant K+W per round).
    state = _compress_tiles_const(state, _node_pad_kw())
    for i in range(8):
        out_ref[0, i] = state[i]


@partial(jax.jit, static_argnames=("interpret",))
def _node_pairs_impl(left, right, interpret):
    p = left.shape[0]
    m = ((p + TILE_M - 1) // TILE_M) * TILE_M
    left = jnp.pad(left.astype(jnp.uint32), ((0, m - p), (0, 0)))
    right = jnp.pad(right.astype(jnp.uint32), ((0, m - p), (0, 0)))
    lp, rp = _to_planes(left), _to_planes(right)
    g = m // TILE_M
    spec = pl.BlockSpec(
        (1, 8, TILE_S, TILE_L), lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        _node_kernel,
        grid=(g,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((g, 8, TILE_S, TILE_L), jnp.uint32),
        interpret=_interpret(interpret),
    )(lp, rp)
    return _from_planes(out)[:p]


def node_pairs_pallas(left, right, interpret=None) -> jax.Array:
    """[P, 8] x [P, 8] digests -> [P, 8] parent digests."""
    if left.shape[0] == 0:
        return jnp.zeros((0, 8), jnp.uint32)
    return _node_pairs_impl(left, right, _interpret(interpret))


# ----------------------------------------------------------- level kernel

def _node_level_kernel(msgs_ref, out_ref):
    """msgs_ref [1, 16, S, L]: the 16-word node message (left || right
    digest) per lane; out [1, 8, S, L]."""
    shape = (msgs_ref.shape[2], msgs_ref.shape[3])
    words = [msgs_ref[0, i] for i in range(16)]
    state = _compress_tiles(_iv_tiles(shape), words)
    state = _compress_tiles_const(state, _node_pad_kw())
    for i in range(8):
        out_ref[0, i] = state[i]


@partial(jax.jit, static_argnames=("interpret",))
def _node_level_impl(cur, interpret):
    p = cur.shape[0] // 2
    # Adjacent rows (2i, 2i+1) ARE the node message left||right: one
    # contiguous reshape, zero data movement — where a left/right split via
    # cur[0::2] / cur[1::2] costs a strided relayout measured at ~17x the
    # kernel itself on a 5M-pair level.
    msgs = cur[: 2 * p].reshape(p, 16)
    m = ((p + TILE_M - 1) // TILE_M) * TILE_M
    msgs = jnp.pad(msgs.astype(jnp.uint32), ((0, m - p), (0, 0)))
    planes = _to_planes(msgs)  # [G, 16, S, L]
    g = m // TILE_M
    out = pl.pallas_call(
        _node_level_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(
                (1, 16, TILE_S, TILE_L), lambda i: (i, 0, 0, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, 8, TILE_S, TILE_L), lambda i: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((g, 8, TILE_S, TILE_L), jnp.uint32),
        interpret=_interpret(interpret),
    )(planes)
    return _from_planes(out)[:p]


def node_level_pallas(cur, interpret=None) -> jax.Array:
    """[M, 8] tree level -> [M//2, 8] parents of ADJACENT pairs (the odd
    tail, when M is odd, is the caller's promotion)."""
    if cur.shape[0] < 2:
        return jnp.zeros((0, 8), jnp.uint32)
    return _node_level_impl(cur, _interpret(interpret))


# ------------------------------------------------------------ tree build

def build_levels_pallas(leaves: jax.Array, interpret=None) -> list[jax.Array]:
    """All tree levels from [N, 8] leaf digests, odd-promotion rule intact.

    Wide levels run the Pallas node kernel; narrow levels (where lane
    padding would dominate) use the scan-based combiner. Bit-identical to
    ``build_levels_device``.
    """
    interp = _interpret(interpret)
    min_pairs = _MIN_PALLAS_PAIRS_INTERP if interp else _MIN_PALLAS_PAIRS
    levels = [leaves]
    cur = leaves
    while cur.shape[0] > 1:
        m = cur.shape[0]
        pairs = m // 2
        if pairs >= min_pairs:
            # Level kernel: consumes adjacent pairs via a contiguous
            # reshape — no even/odd strided split (a ~17x relayout cost).
            nxt = node_level_pallas(cur, interpret=interp)
        else:
            nxt = sha256_node_pairs(cur[0 : 2 * pairs : 2],
                                    cur[1 : 2 * pairs : 2])
        if m % 2:
            nxt = jnp.concatenate([nxt, cur[-1:]], axis=0)
        levels.append(nxt)
        cur = nxt
    return levels


def tree_root_pallas(leaves: jax.Array, interpret=None) -> jax.Array:
    """[N, 8] leaf digests -> [8] root digest (N >= 1)."""
    return build_levels_pallas(leaves, interpret=interpret)[-1][0]
