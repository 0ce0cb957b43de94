"""Pallas kernel microbenchmarks (real TPU): one JSON line per kernel.

Times the SHA-256 kernels in isolation plus both node-hash formulations at
a wide tree level, so kernel regressions are attributable without rerunning
the full north-star bench:

- leaf_digests_pallas   [N, B, 16] blocks -> [N, 8]
- node_pairs_pallas     strided even/odd split + pair kernel (the cost the
                        level kernel exists to avoid)
- node_level_pallas     contiguous adjacent-pair level kernel
- scan baselines        the portable lax.scan formulation for both shapes

Timing follows bench.py's discipline: each rep's
input is salted with the previous rep's output (defeats backend result
caching) and synchronization is a single tiny row fetch, not a bulk copy
of the result (a [4M, 8] fetch would otherwise dominate the kernel time).

Off-TPU this prints the scan baselines only, at smoke sizes. Interpret-mode
Pallas is NOT exercised: lowering the 64 unrolled rounds through the
interpreter takes XLA tens of minutes to compile even at tiny sizes (the
same reason kernel tests are TPU-gated in tests/test_sha256_pallas.py).

Usage:
    python tools/kernel_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# Runnable as `python tools/kernel_bench.py` from anywhere: the package
# lives at the repo root, one level up from this file.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_salted(make_step, reps: int | None = None) -> float:
    """Median wall seconds per call.

    ``make_step() -> (step, salt0)`` where ``step(salt) -> out`` is jitted,
    folds the salt into its input, and returns an array whose first row
    feeds the next rep's salt. Sync is the 1-row fetch of that output.
    """
    if reps is None:
        reps = int(os.environ.get("MKV_KB_REPS", "20"))
    step, salt = make_step()
    out = step(salt)
    np.asarray(out[:1])  # compile + sync
    times = []
    for _ in range(reps):
        salt = out[0]
        t0 = time.perf_counter()
        out = step(salt)
        np.asarray(out[:1])
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from merklekv_tpu.merkle.packing import pack_leaves
    from merklekv_tpu.ops import sha256_pallas as sp
    from merklekv_tpu.ops.sha256 import sha256_blocks, sha256_node_pairs

    on_tpu = jax.default_backend() == "tpu"
    n = (1 << 22) if on_tpu else (1 << 10)  # 4M leaves / 2M pairs on chip

    rows = []

    # Leaf hashing: [n, B, 16] blocks. Salt perturbs one message word of
    # block 0 (the digest changes; the valid-block masking is untouched).
    keys = [b"kb:%09d" % i for i in range(n)]
    values = [b"v-%d" % (i % 7919) for i in range(n)]
    packed = pack_leaves(keys, values)
    blocks = jax.device_put(packed.blocks)
    nblocks = jax.device_put(packed.nblocks)

    def leaf_maker(hash_fn):
        def make():
            @jax.jit
            def step(salt):
                b = blocks.at[0, 0, :8].set(blocks[0, 0, :8] ^ salt)
                return hash_fn(b, nblocks)

            return step, jnp.zeros(8, jnp.uint32)

        return make

    if on_tpu:
        dt = _time_salted(leaf_maker(sp.leaf_digests_pallas))
        rows.append({"kernel": "leaf_digests_pallas", "n": n,
                     "keys_per_s": round(n / dt, 1), "ms": round(dt * 1e3, 3)})
    dt = _time_salted(leaf_maker(sha256_blocks))
    rows.append({"kernel": "sha256_blocks_scan", "n": n,
                 "keys_per_s": round(n / dt, 1), "ms": round(dt * 1e3, 3)})

    # Node formulations at one wide level: [n, 8] -> [n//2, 8]. Salt
    # perturbs row 0, so every rep hashes fresh data.
    rng = np.random.RandomState(5)
    level = jax.device_put(
        rng.randint(0, 2**32, (n, 8), dtype=np.uint64).astype(np.uint32)
    )
    pairs = n // 2

    def level_maker(level_fn):
        def make():
            @jax.jit
            def step(salt):
                c = level.at[0].set(level[0] ^ salt)
                return level_fn(c)

            return step, jnp.zeros(8, jnp.uint32)

        return make

    if on_tpu:
        dt = _time_salted(level_maker(sp.node_level_pallas))
        rows.append({"kernel": "node_level_pallas", "pairs": pairs,
                     "pairs_per_s": round(pairs / dt, 1), "ms": round(dt * 1e3, 3)})
        dt = _time_salted(
            level_maker(lambda c: sp.node_pairs_pallas(c[0::2], c[1::2]))
        )
        rows.append({"kernel": "node_pairs_pallas_strided", "pairs": pairs,
                     "pairs_per_s": round(pairs / dt, 1), "ms": round(dt * 1e3, 3)})
    dt = _time_salted(
        level_maker(lambda c: sha256_node_pairs(c[0::2], c[1::2]))
    )
    rows.append({"kernel": "sha256_node_pairs_scan", "pairs": pairs,
                 "pairs_per_s": round(pairs / dt, 1), "ms": round(dt * 1e3, 3)})

    # Full tree build through the production dispatch (root is [8]; the
    # final level IS the tiny fetch).
    from merklekv_tpu.ops.dispatch import build_levels

    leaves = (sp.leaf_digests_pallas(blocks, nblocks) if on_tpu
              else sha256_blocks(blocks, nblocks))
    leaves = jax.device_put(np.asarray(leaves))

    def build_maker():
        @jax.jit
        def step(salt):
            lv = leaves.at[0].set(leaves[0] ^ salt)
            return build_levels(lv)[-1]

        return step, jnp.zeros(8, jnp.uint32)

    dt = _time_salted(build_maker)
    rows.append({"kernel": "build_levels_dispatch", "n": n,
                 "leaves_per_s": round(n / dt, 1), "ms": round(dt * 1e3, 3)})

    for r in rows:
        r["backend"] = jax.default_backend()
        print(json.dumps(r))
    if not on_tpu:
        print("# off-TPU smoke run: scan baselines only", file=sys.stderr)


if __name__ == "__main__":
    main()
