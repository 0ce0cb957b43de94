"""Vectorized multi-replica Merkle diff.

The reference diffs two trees by walking a flat leaf map pairwise on the host
(/root/reference/src/store/merkle.rs:171-196) and reconciles one peer at a
time over per-key TCP GETs (/root/reference/src/sync.rs:56-214). Here the
whole comparison is one XLA program over stacked replica tensors:

  - N replicas' leaf digests are aligned host-side onto the union keyspace
    (sorted keys; absent keys get a presence-mask 0);
  - the device computes per-key divergence masks for all replicas against a
    reference replica simultaneously — [R, N] in one fused elementwise pass;
  - winners for reconciliation (LWW at a higher layer) come back as index
    lists, not values — values never travel through the diff.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "AlignedReplicas",
    "align_replicas",
    "divergence_vs_ref",
    "divergence_masks",
    "divergence_masks_engine",
    "diff_keys_multi",
    "diff_keys_pair",
]

# Route the [R, N] comparison through the keyspace-sharded SPMD program
# only past this union-keyspace size: below it the collective setup costs
# more than the elementwise pass it parallelizes.
SHARDED_DIFF_MIN_KEYS = 1 << 15


class AlignedReplicas:
    """Union-keyspace alignment of R replicas' (key -> leaf digest) maps.

    Attributes:
      keys:    union keyspace, sorted bytes, length N.
      digests: [R, N, 8] uint32 — leaf digest per replica/key (0 if absent).
      present: [R, N] bool — key present in replica r.
    """

    __slots__ = ("keys", "digests", "present")

    def __init__(self, keys: list[bytes], digests: np.ndarray, present: np.ndarray):
        self.keys = keys
        self.digests = digests
        self.present = present

    @property
    def n_replicas(self) -> int:
        return self.digests.shape[0]

    @property
    def n_keys(self) -> int:
        return self.digests.shape[1]


def align_replicas(replicas: Sequence[dict[bytes, bytes]]) -> AlignedReplicas:
    """Align R (key -> 32-byte leaf hash) maps onto the sorted union keyspace."""
    union: set[bytes] = set()
    for r in replicas:
        union.update(r.keys())
    keys = sorted(union)
    n = len(keys)
    r_count = len(replicas)
    idx = {k: i for i, k in enumerate(keys)}
    digests = np.zeros((r_count, n, 8), np.uint32)
    present = np.zeros((r_count, n), bool)
    for ri, rep in enumerate(replicas):
        for k, h in rep.items():
            i = idx[k]
            digests[ri, i] = np.frombuffer(h, ">u4").astype(np.uint32)
            present[ri, i] = True
    return AlignedReplicas(keys, digests, present)


def divergence_vs_ref(digests, present, ref_d, ref_p):
    """THE divergence predicate, in one place: a key diverges iff presence
    differs or both present with different digests. Polymorphic over numpy
    and jax arrays (method-call formulation, no jnp/np entry points) so the
    device programs and the host twin cannot drift apart. Deliberately NOT
    jitted: divergence_masks_np must stay pure-host (spawned server
    processes may not initialize an accelerator backend), and the device
    callers already jit at their own program boundaries."""
    same_digest = (digests == ref_d).all(axis=-1)
    both_present = present & ref_p
    return (present != ref_p) | (both_present & ~same_digest)


def divergence_masks(digests: jax.Array, present: jax.Array) -> jax.Array:
    """[R, N] bool: key i diverges between replica r and replica 0.

    Row 0 is all-False by construction.
    """
    return divergence_vs_ref(digests, present, digests[0:1], present[0:1])


def divergence_masks_np(digests: np.ndarray, present: np.ndarray) -> np.ndarray:
    """Host-side twin of :func:`divergence_masks` for small keyspaces where
    initializing an accelerator backend is not worth it (and, in spawned
    server processes, must be avoided unless explicitly configured)."""
    return divergence_vs_ref(digests, present, digests[0:1], present[0:1])


def _local_diff_mesh():
    """One-axis ``key`` mesh over the largest power-of-two local-device
    subset, or None on a single-device host. Deferred import: parallel/
    imports this module, so the dependency must stay call-time."""
    from merklekv_tpu.parallel.mesh import make_mesh
    from merklekv_tpu.parallel.sharded_state import resolve_shard_count

    devs = jax.local_devices()
    n = resolve_shard_count("auto", len(devs))  # 0 on a 1-device host
    if n < 2:
        return None
    return make_mesh({"key": n}, devices=devs[:n])


def divergence_masks_engine(
    digests, present, min_keys: Optional[int] = None
) -> jax.Array:
    """The N-replica diff behind the engine boundary.

    Routes the ``[R, N]`` comparison through the keyspace-sharded SPMD
    program (``parallel.sharded_merkle.sharded_divergence``) when the host
    has a multi-device mesh and the union keyspace amortizes the
    collectives; single-device :func:`divergence_masks` otherwise. Masks
    are bit-identical either way. The key axis is padded up to the mesh
    axis with all-absent columns (absent everywhere == absent on the
    reference -> never divergent) and sliced back off.

    ``min_keys`` overrides :data:`SHARDED_DIFF_MIN_KEYS` (0 forces the
    sharded path whenever a mesh exists — tests and the bench sweep).
    """
    n = int(digests.shape[1])
    lim = SHARDED_DIFF_MIN_KEYS if min_keys is None else min_keys
    mesh = None
    if n > 0 and n >= lim:
        try:
            mesh = _local_diff_mesh()
        except Exception:
            mesh = None
    if mesh is None:
        return divergence_masks(digests, present)
    from merklekv_tpu.device.guard import DeviceDispatchError, get_guard
    from merklekv_tpu.parallel.sharded_merkle import divergence_program

    d = int(mesh.shape["key"])
    pad = (-n) % d
    if pad:
        dig = np.concatenate(
            [np.asarray(digests),
             np.zeros((digests.shape[0], pad, 8), np.uint32)], axis=1
        )
        pres = np.concatenate(
            [np.asarray(present),
             np.zeros((present.shape[0], pad), bool)], axis=1
        )
    else:
        dig, pres = digests, present
    try:
        # Deadline-guarded like every serving-path device program: a sick
        # mesh fails the dispatch at the guard instead of wedging the
        # anti-entropy walk. Label follows the documented shard{N}_*
        # scheme so chaos globs targeting the sharded rungs (shard*,
        # shard8_*) reach this seam too.
        masks, _counts = get_guard().call(
            f"shard{d}_diff", divergence_program(mesh, "key"),
            jnp.asarray(dig), jnp.asarray(pres),
        )
    except DeviceDispatchError:
        # The sharded program is an optimization, never the contract: the
        # single-device comparison is bit-identical, so a faulted mesh
        # sheds parallelism here, not the sync plane.
        return divergence_masks(digests, present)
    return masks[:, :n] if pad else masks


@jax.jit
def _any_divergent(digests: jax.Array, present: jax.Array) -> jax.Array:
    """[N] bool: key diverges between ANY pair of replicas (union view)."""
    masks = divergence_masks(digests, present)
    return jnp.any(masks, axis=0)


def diff_keys_multi(aligned: AlignedReplicas) -> dict[int, list[bytes]]:
    """Per-replica divergent key lists vs replica 0, computed in one program."""
    if aligned.n_keys == 0:
        return {r: [] for r in range(1, aligned.n_replicas)}
    masks = np.asarray(divergence_masks(aligned.digests, aligned.present))
    out: dict[int, list[bytes]] = {}
    for r in range(1, aligned.n_replicas):
        (ii,) = np.nonzero(masks[r])
        out[r] = [aligned.keys[i] for i in ii]
    return out


def diff_keys_pair(
    local: dict[bytes, bytes], remote: dict[bytes, bytes]
) -> list[bytes]:
    """Sorted keys differing between two leaf-hash maps (reference
    merkle.rs:171-196 semantics), via the batched device path."""
    aligned = align_replicas([local, remote])
    return diff_keys_multi(aligned).get(1, [])
