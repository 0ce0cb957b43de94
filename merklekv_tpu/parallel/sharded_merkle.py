"""SPMD Merkle build and diff over a device mesh (shard_map + collectives).

Decomposition: for N = D * L leaves with L a power of two, the bottom
log2(L) tree levels never cross a shard boundary — every pair merge is
inside one contiguous block of L sorted leaves. So each device reduces its
own [L, 8] leaf block to one subtree root locally (pure pairwise, no
promotions), the D subtree roots are all_gathered over ICI, and the tiny
top tree over D nodes is computed redundantly on every device (D-1 hashes).
The result is bit-identical to the single-device odd-promotion tree of N
leaves, because D and L are powers of two here.

Divergence is embarrassingly parallel over keys: each device compares its
[R, L] digest block and psums the per-replica divergence counts so every
shard returns the global count alongside its local mask block.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from merklekv_tpu.merkle.diff import divergence_masks, divergence_vs_ref
from merklekv_tpu.ops.dispatch import build_levels, hash_blocks, use_pallas

__all__ = [
    "sharded_tree_root",
    "sharded_divergence",
    "divergence_program",
    "sharded_divergence_2d",
    "sharded_anti_entropy_step",
    "make_anti_entropy_step",
    "padded_level_specs",
    "sharded_levels_program",
    "sharded_scatter_program",
    "sharded_restructure_program",
]


def _local_root(block: jax.Array) -> jax.Array:
    """[L, 8] -> [1, 8] subtree root (L is a power of two). Node hashing is
    backend-dispatched: Pallas kernels on TPU, scan elsewhere."""
    return build_levels(block)[-1]


def _check_local_block(l: int) -> None:
    """Trace-time guard: per-shard leaf count must be a positive power of two,
    or the local subtree reduction would apply odd-promotion at a shard
    boundary and silently diverge from the global tree."""
    if l == 0 or (l & (l - 1)):
        raise ValueError(
            f"per-shard leaf count {l} must be a positive power of two"
        )


def _check_shardable(n: int, d: int, what: str = "leaf count") -> int:
    """Validate n = d * L with L a positive power of two; return L."""
    if n % d:
        raise ValueError(f"{what} {n} not divisible by mesh axis {d}")
    l = n // d
    if l == 0 or (l & (l - 1)):
        raise ValueError(f"per-shard {what} {l} must be a positive power of two")
    return l


@lru_cache(maxsize=None)
def _tree_root_program(mesh: Mesh, axis: str, pallas: bool):
    del pallas  # cache key only; the dispatch is re-read at trace time

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(None, None),
        check_vma=False,
    )
    def mkv_shard_tree_root(block):
        with jax.named_scope("mkv_shard_tree_root"):
            _check_local_block(block.shape[0])
            local = _local_root(block)  # [1, 8]
            # [D, 8] shard roots, then [1, 8], the same on every shard.
            roots = jax.lax.all_gather(local, axis, axis=0, tiled=True)
            return build_levels(roots)[-1]

    return jax.jit(mkv_shard_tree_root)


def sharded_tree_root(mesh: Mesh, leaves: jax.Array, axis: str = "key") -> jax.Array:
    """Root of the Merkle tree over [N, 8] leaf digests, keyspace-sharded.

    N must equal mesh.shape[axis] * L with L a power of two (pad the
    keyspace tensor to a bucket boundary before calling). Returns [8] uint32,
    bit-identical to ``tree_root(leaves)``. The compiled SPMD program is
    cached per (mesh, axis, shapes).
    """
    _check_shardable(leaves.shape[0], mesh.shape[axis])
    return _tree_root_program(mesh, axis, use_pallas())(leaves)[0]


def sharded_divergence(
    mesh: Mesh,
    digests: jax.Array,
    present: jax.Array,
    axis: str = "key",
) -> tuple[jax.Array, jax.Array]:
    """Keyspace-sharded multi-replica divergence.

    digests: [R, N, 8] uint32; present: [R, N] bool; N divisible by the mesh
    axis. Returns (masks [R, N] bool — sharded over keys, counts [R] int32 —
    global via psum, replicated).
    """
    if digests.shape[1] % mesh.shape[axis]:
        raise ValueError("key axis not divisible by mesh")
    return divergence_program(mesh, axis)(digests, present)


@lru_cache(maxsize=None)
def divergence_program(mesh: Mesh, axis: str):
    """The jitted program behind :func:`sharded_divergence`, one per
    (mesh, axis): (digests, present) -> (masks, counts)."""
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis, None), P(None, axis)),
        out_specs=(P(None, axis), P(None)),
        check_vma=False,
    )
    def mkv_shard_divergence(dig, pres):
        with jax.named_scope("mkv_shard_divergence"):
            masks = divergence_masks(dig, pres)
            counts = jax.lax.psum(
                jnp.sum(masks, axis=1, dtype=jnp.int32), axis
            )
            return masks, counts

    return jax.jit(mkv_shard_divergence)


def sharded_divergence_2d(
    mesh: Mesh,
    digests: jax.Array,
    present: jax.Array,
    replica_axis: str = "replica",
    key_axis: str = "key",
) -> tuple[jax.Array, jax.Array]:
    """Replica-AND-keyspace-sharded divergence for large fleets.

    :func:`sharded_divergence` shards only the key axis, holding all R
    replicas' digests on every device — at BASELINE config 5 scale (64
    replicas x large N) that is the memory ceiling. Over a 2-D
    ``(replica, key)`` mesh each device holds an [R/Dr, N/Dk] block: masks
    come back sharded the same way, and per-replica counts psum over the
    key axis only (each replica row is owned by one replica-shard, so no
    cross-replica reduction is needed or performed).

    digests [R, N, 8] uint32, present [R, N] bool; R and N divisible by
    their mesh axes. Returns (masks [R, N] bool — sharded over both axes,
    counts [R] int32 — sharded over replicas, replicated over keys).
    Reference replica 0 lives in the first replica shard; each device
    gathers just one digest row per replica shard along the replica axis
    (Dr rows, not R) to obtain replica 0's block for its keys.
    """
    r, n = digests.shape[0], digests.shape[1]
    dr, dk = mesh.shape[replica_axis], mesh.shape[key_axis]
    if r % dr:
        raise ValueError(f"replica count {r} not divisible by mesh axis {dr}")
    if n % dk:
        raise ValueError(f"key count {n} not divisible by mesh axis {dk}")
    return _divergence_2d_program(mesh, replica_axis, key_axis)(
        digests, present
    )


@lru_cache(maxsize=None)
def _divergence_2d_program(mesh: Mesh, replica_axis: str, key_axis: str):
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(replica_axis, key_axis, None),
            P(replica_axis, key_axis),
        ),
        out_specs=(P(replica_axis, key_axis), P(replica_axis)),
        check_vma=False,
    )
    def mkv_shard_divergence_2d(dig, pres):
        with jax.named_scope("mkv_shard_divergence_2d"):
            # Reference digests: global replica row 0, held by the first
            # replica shard. Gather ONE row per replica shard — [Dr,
            # n_local, 8] — and take shard 0's, NOT the full [R, n_local,
            # 8] blocks (re-materializing those on every device would
            # rebuild exactly the per-device footprint this 2-D program
            # exists to avoid).
            ref = jax.lax.all_gather(
                dig[:1], replica_axis, axis=0, tiled=True
            )[0]  # [n_local, 8]
            ref_pres = jax.lax.all_gather(
                pres[:1], replica_axis, axis=0, tiled=True
            )[0]  # [n_local]
            masks = divergence_vs_ref(dig, pres, ref[None], ref_pres[None])
            counts = jax.lax.psum(
                jnp.sum(masks, axis=1, dtype=jnp.int32), key_axis
            )
            return masks, counts

    return jax.jit(mkv_shard_divergence_2d)


def make_anti_entropy_step(mesh: Mesh, axis: str = "key", pallas=None):
    """One fused SPMD anti-entropy program over a keyspace-sharded mesh.

    The full data-plane step of the framework (the analog of a training step):
      1. hash every local (key, value) leaf — batched SHA-256 over the shard's
         padded block tensor;
      2. reduce the local leaves to one subtree root, all_gather the D subtree
         roots over ICI, finish the tiny top tree on every shard (the
         per-shard leaf count must be a positive power of two — enforced at
         trace time);
      3. compare R replicas' digest blocks elementwise and psum the global
         per-replica divergence counts.

    Replaces the reference's host-side per-key sync loop
    (/root/reference/src/sync.rs:56-214) with one compiled XLA program.

    Inputs (global shapes):
      blocks  [N, B, 16] uint32 — padded SHA-256 blocks, keyspace-sharded
      nblocks [N] int32         — valid block count per leaf
      digests [R, N, 8] uint32  — R replicas' leaf digests (replicated over R)
      present [R, N] bool
    Returns (root [8] uint32 replicated, masks [R, N] bool sharded over keys,
    counts [R] int32 replicated).

    ``pallas`` keys the program cache on the SHA-256 backend; None (the
    default) resolves the dispatch at CALL time — Pallas on TPU, scan
    elsewhere — outside the cache, so an env flip between calls can never
    replay a program compiled for the other formulation.
    """
    return _anti_entropy_program(
        mesh, axis, use_pallas() if pallas is None else pallas
    )


@lru_cache(maxsize=None)
def _anti_entropy_program(mesh: Mesh, axis: str, pallas: bool):
    del pallas  # cache key only; the dispatch is re-read at trace time

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None, None), P(axis), P(None, axis, None), P(None, axis)),
        out_specs=(P(None), P(None, axis), P(None)),
        check_vma=False,
    )
    def mkv_shard_anti_entropy_step(blk, nb, dig, pres):
        with jax.named_scope("mkv_shard_anti_entropy_step"):
            _check_local_block(blk.shape[0])
            leaves = hash_blocks(blk, nb)  # Pallas on TPU, scan elsewhere
            local_root = _local_root(leaves)  # [1, 8]
            roots = jax.lax.all_gather(
                local_root, axis, axis=0, tiled=True
            )  # [D, 8]
            root = build_levels(roots)[-1][0]  # [8]
            masks = divergence_masks(dig, pres)
            counts = jax.lax.psum(
                jnp.sum(masks, axis=1, dtype=jnp.int32), axis
            )
            return root, masks, counts

    return jax.jit(mkv_shard_anti_entropy_step)


def sharded_anti_entropy_step(
    mesh: Mesh,
    blocks: jax.Array,
    nblocks: jax.Array,
    digests: jax.Array,
    present: jax.Array,
    axis: str = "key",
):
    """Run the fused hash+build+diff step (see :func:`make_anti_entropy_step`)."""
    d = mesh.shape[axis]
    _check_shardable(blocks.shape[0], d)
    if digests.shape[1] != blocks.shape[0]:
        raise ValueError(
            f"digest key axis {digests.shape[1]} != leaf count {blocks.shape[0]}"
        )
    return make_anti_entropy_step(mesh, axis, use_pallas())(
        blocks, nblocks, digests, present
    )


# --------------------------------------------------------------------------
# Serving-tree SPMD programs (the ShardedDeviceMerkleState backend).
#
# The padded tree at capacity C = 2^d over a D-way mesh decomposes exactly
# like the standalone root program above: per-shard leaf blocks of L = C/D
# (a power of two) reduce to shard-local subtree levels with NO cross-shard
# hash — every pair merge at a level of size >= D lives inside one shard's
# contiguous block, so concatenating the shard blocks IS the global padded
# level. Only the log2(D) top levels (sizes D/2 .. 1) combine across
# shards: one all_gather of the D shard roots over ICI, then the tiny top
# tree — computed redundantly on every shard (D-1 hashes), following the
# parallel-first wide-top decomposition of "Note on Optimal Trees for
# Parallel Hash Functions" (arxiv 1604.04206) / "Optimal Tree Hash Modes"
# (arxiv 1607.00307). The returned tuple therefore has the SAME layout as
# the single-device padded tree (level j is [C >> j, 8]), so every
# promotion-chain query (root, TREELEVEL) runs unchanged and bit-identical.


def padded_level_specs(capacity: int, d: int, axis: str) -> tuple:
    """Per-level PartitionSpecs of the padded tree over a D-way mesh:
    levels of size >= D stay keyspace-sharded; the top tree (size < D) is
    replicated on every shard."""
    specs = []
    size = capacity
    while size >= 1:
        specs.append(P(axis, None) if size >= d else P(None, None))
        size //= 2
    return tuple(specs)


def _local_level_count(capacity: int, d: int) -> int:
    """Shard-local padded levels (sizes C .. D): log2(C/D) + 1."""
    return (capacity // d).bit_length()


def _reduce_padded(leaves: jax.Array) -> tuple:
    """All padded-tree levels bottom-up (power-of-two input, no odd tail);
    node hashing is backend-dispatched like merkle/incremental.py."""
    from merklekv_tpu.ops.dispatch import hash_node_level

    levels = [leaves]
    cur = leaves
    while cur.shape[0] > 1:
        cur = hash_node_level(cur)
        levels.append(cur)
    return tuple(levels)


@lru_cache(maxsize=None)
def _levels_body(mesh: Mesh, axis: str, capacity: int):
    """shard_map body: [C, 8] keyspace-sharded leaves -> every padded
    level. Not jitted — composed both standalone (build) and inside the
    restructure program."""
    d = mesh.shape[axis]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=padded_level_specs(capacity, d, axis),
        check_vma=False,
    )
    def mkv_shard_build_levels(block):  # [L, 8] local leaf slice
        with jax.named_scope("mkv_shard_build_levels"):
            local = _reduce_padded(block)  # sizes L .. 1
            roots = jax.lax.all_gather(local[-1], axis, axis=0, tiled=True)
            # Sizes D/2 .. 1 (empty when D=1).
            top = _reduce_padded(roots)[1:]
            return (*local, *top)

    return mkv_shard_build_levels


@lru_cache(maxsize=None)
def sharded_levels_program(mesh: Mesh, axis: str, capacity: int, pallas: bool):
    """Compiled sharded padded-tree build: per-shard subtrees reduce in
    parallel, shard roots combine via all_gather + the wide top tree."""
    del pallas  # cache key only; the dispatch is re-read at trace time
    return jax.jit(_levels_body(mesh, axis, capacity))


@lru_cache(maxsize=None)
def sharded_scatter_program(
    mesh: Mesh, axis: str, capacity: int, kb: int, pallas: bool
):
    """Per-shard-routed incremental update: ONE SPMD program scatters each
    shard's routed sub-batch of fresh leaf digests into the shard-local
    leaf slice, re-reduces only the touched parent paths, and rebuilds the
    tiny top tree from the all_gathered shard roots.

    Inputs are ROUTED host-side ([D, kb, ...] arrays sharded on dim 0, so
    each device receives only its own sub-batch): ``idx`` holds SHARD-LOCAL
    leaf positions with L (one past the slice) as the padding sentinel —
    padded rows scatter into a scratch row appended per level and dropped
    from the output, so a shard with fewer (or zero) updates dispatches the
    same program with no-op rows instead of forcing a ragged shape.
    """
    del pallas
    d = mesh.shape[axis]
    l = capacity // d
    specs = padded_level_specs(capacity, d, axis)
    n_local = _local_level_count(capacity, d)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            *specs[:n_local],
            P(axis, None),        # idx      [D, kb]
            P(axis, None, None),  # digests  [D, kb, 8]
        ),
        out_specs=specs,
        check_vma=False,
    )
    def mkv_shard_scatter(*args):
        with jax.named_scope("mkv_shard_scatter"):
            levels, (idx, digests) = args[:n_local], args[n_local:]
            from merklekv_tpu.ops.dispatch import hash_node_pairs as _pairs

            new = digests[0]  # [kb, 8]
            # [kb] local positions; pads already == scratch (L).
            tgt = idx[0]
            scratch = jnp.zeros((1, 8), jnp.uint32)
            child = jnp.concatenate([levels[0], scratch]).at[tgt].set(new)
            out = [child[:-1]]
            cur = tgt
            for j in range(1, n_local):
                size = levels[j].shape[0]  # l >> j
                # Parent path; pads carry through to each level's scratch
                # slot.
                cur = jnp.minimum(cur // 2, size)
                # Children read from the UPDATED child level; a pad's
                # children (2*size, 2*size+1) hit the scratch row / clamp
                # out of range — garbage hashed into scratch, dropped below.
                parents = _pairs(child[2 * cur], child[2 * cur + 1])
                child = jnp.concatenate([levels[j], scratch]).at[cur].set(
                    parents
                )
                out.append(child[:-1])
            roots = jax.lax.all_gather(out[-1], axis, axis=0, tiled=True)
            top = _reduce_padded(roots)[1:]
            return (*out, *top)

    return jax.jit(mkv_shard_scatter)


@lru_cache(maxsize=None)
def sharded_restructure_program(
    mesh: Mesh, axis: str, c_old: int, c_new: int, kb: int, pallas: bool
):
    """Compiled shape change over the mesh: cross-shard gather of surviving
    leaf digests into their shifted slots (GSPMD inserts the collective
    permute), scatter of the kb fresh digests, then the per-shard subtree
    reduction + all_gather top tree — survivors never rehash, exactly like
    the single-device restructure."""
    del pallas
    leaf_spec = NamedSharding(mesh, P(axis, None))
    body = _levels_body(mesh, axis, c_new)

    @jax.jit
    def mkv_shard_restructure(old_leaves, gather_idx, fresh_pos, fresh):
        with jax.named_scope("mkv_shard_restructure"):
            safe = jnp.clip(gather_idx, 0, max(c_old - 1, 0))
            base = jnp.where((gather_idx >= 0)[:, None], old_leaves[safe], 0)
            if kb:
                base = base.at[fresh_pos].set(fresh)
            base = jax.lax.with_sharding_constraint(base, leaf_spec)
            return body(base)

    return mkv_shard_restructure
