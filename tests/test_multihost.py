"""Multi-host (DCN) SPMD: two real processes, one global mesh.

Spawns 2 worker processes, each with 4 virtual CPU devices; they form an
8-device jax cluster (jax.distributed) via merklekv_tpu.parallel.multihost,
lift host-local keyspace shards into global arrays, and run the fused
anti-entropy step — the cross-process analog of the reference's multi-node
sync fabric (/root/reference/src/sync.rs:150-214). Both processes must
report the SAME root, equal to the single-process CPU golden root over the
full keyspace, and the psum'd divergence counts must match the seeded
divergence.
"""

import socket
import subprocess
import sys

import numpy as np
import pytest

N_GLOBAL = 64  # keyspace size; 8 leaves per device on the 8-device mesh
R = 3          # replicas in the diff
DIVERGED = 5   # seeded divergent keys on replica 1

_WORKER = r"""
import os, sys
import jax
sys.path.insert(0, {repo!r})

from merklekv_tpu.parallel import multihost, sharded_anti_entropy_step

pid = int(os.environ["MKV_PROCESS_ID"])
multihost.initialize()
assert multihost.is_initialized() and multihost.process_count() == 2

import numpy as np
from merklekv_tpu.merkle.jax_engine import leaf_digests
from merklekv_tpu.merkle.packing import pack_leaves

N, R, DIVERGED = {n}, {r}, {diverged}
keys = [b"mh:%05d" % i for i in range(N)]
values = [b"val-%d" % i for i in range(N)]

# Global truth, built identically on both processes (cheap at this size);
# each process then keeps only its contiguous half as ITS host-local rows.
packed = pack_leaves(keys, values)
digests = np.tile(np.asarray(leaf_digests(keys, values))[None], (R, 1, 1))
present = np.ones((R, N), bool)
digests[1, :DIVERGED, 0] ^= 0xDEAD  # replica 1 diverges on DIVERGED keys

lo, hi = (0, N // 2) if pid == 0 else (N // 2, N)
mesh = multihost.global_key_mesh()
blocks_g, nblocks_g, digests_g, present_g = multihost.lift_local_shards(
    mesh,
    packed.blocks[lo:hi],
    packed.nblocks[lo:hi],
    digests[:, lo:hi],
    present[:, lo:hi],
)
root, masks, counts = sharded_anti_entropy_step(
    mesh, blocks_g, nblocks_g, digests_g, present_g
)
from merklekv_tpu.ops.sha256 import digest_to_bytes

print("ROOT", digest_to_bytes(np.asarray(root)).hex(), flush=True)
print("COUNTS", ",".join(map(str, np.asarray(counts))), flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.integration
def test_two_process_cluster_agrees_with_golden(tmp_path):
    # Formerly xfail("Multiprocess computations aren't implemented on the
    # CPU backend") — that XlaRuntimeError came from executing the
    # all_gather/psum collectives with no cross-process CPU collectives
    # implementation configured. multihost.initialize now selects gloo
    # (jax_cpu_collectives_implementation) before jax.distributed
    # initializes, and the 2-process SPMD step runs for real.
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = tmp_path / "mh_worker.py"
    worker.write_text(
        _WORKER.format(repo=repo, n=N_GLOBAL, r=R, diverged=DIVERGED)
    )
    port = _free_port()
    procs = []
    env_base = {
        k: v
        for k, v in os.environ.items()
        # Workers pick their own device count; drop the suite's 8-device
        # flag.
        if k != "XLA_FLAGS"
    }
    for pid in range(2):
        env = dict(
            env_base,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            MKV_COORDINATOR=f"127.0.0.1:{port}",
            MKV_NUM_PROCESSES="2",
            MKV_PROCESS_ID=str(pid),
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(worker)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
            outs.append(out)
    finally:
        # A dead coordinator leaves the other worker blocked in
        # jax.distributed.initialize — never orphan it on a failure path.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)

    roots, counts = [], []
    for out in outs:
        lines = dict(
            line.split(" ", 1) for line in out.strip().splitlines()
            if line.startswith(("ROOT", "COUNTS"))
        )
        roots.append(lines["ROOT"])
        counts.append(lines["COUNTS"])

    # Same replicated root and counts on every host.
    assert roots[0] == roots[1]
    assert counts[0] == counts[1] == f"0,{DIVERGED},0"

    # Cross-check against the single-process golden root (CPU core).
    from merklekv_tpu.merkle.cpu import build_levels
    from merklekv_tpu.merkle.encoding import leaf_hash

    keys = [b"mh:%05d" % i for i in range(N_GLOBAL)]
    values = [b"val-%d" % i for i in range(N_GLOBAL)]
    golden = build_levels([leaf_hash(k, v) for k, v in zip(keys, values)])[-1][0]
    assert roots[0] == golden.hex()


def test_initialize_requires_full_topology(monkeypatch):
    """Coordinator without process count / rank must fail with a clear
    configuration error, not a raw KeyError."""
    from merklekv_tpu.parallel import multihost

    monkeypatch.delenv("MKV_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("MKV_PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="MKV_NUM_PROCESSES"):
        multihost.initialize(coordinator="127.0.0.1:1")
    with pytest.raises(ValueError, match="MKV_PROCESS_ID"):
        multihost.initialize(coordinator="127.0.0.1:1", num_processes=2)
