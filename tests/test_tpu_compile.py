"""Chip compiles at real widths, for a described (not attached) TPU v5e.

The TPU compiler is installed with jax; it compiles for a topology that is
only described, so these tests catch what interpret mode cannot — a kernel
that needs more VMEM than a core has, a block shape the tiling refuses, a
sharded program that cannot be partitioned — without a chip. Nothing runs.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, so the worker that is
handed this file loads it, and the fixture skips where it cannot.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described-device compile can be written to the persistent cache but
    # not read back without a chip: keep the cache out of these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "rows,blocks",
    [
        (2048, 1),  # one-block leaves
        (2048, 17),  # 1 KB YCSB records
        (1, 16_385),  # a 1 MiB value: the chunked block loop
        (1, 160_001),  # a 10 MB value
    ],
)
def test_leaf_kernel_compiles_for_v5e(one_chip, rows, blocks):
    from merklekv_tpu.ops import sha256_pallas as sp

    compiled = sp._leaf_digests_impl.lower(
        _spec((rows, blocks, 16), jnp.uint32, one_chip),
        _spec((rows,), jnp.int32, one_chip),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # Per-call memory follows LEAF_CHUNK_BLOCKS, not the message length.
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30


def test_node_level_kernel_compiles_for_v5e_at_2_20_pairs(one_chip):
    from merklekv_tpu.ops import sha256_pallas as sp

    compiled = sp._node_level_impl.lower(
        _spec((2 << 20, 8), jnp.uint32, one_chip), interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_diff_compiles_for_v5e_2x2(topo):
    """The 8-replica divergence program over the four described chips:
    keys sharded on the mesh, per-replica counts reduced with a psum."""
    from merklekv_tpu.parallel.sharded_merkle import divergence_program

    mesh = Mesh(np.array(topo.devices[:4]), ("key",))
    r, n = 8, 1 << 20
    compiled = divergence_program(mesh, "key").lower(
        _spec((r, n, 8), jnp.uint32, NamedSharding(mesh, P(None, "key", None))),
        _spec((r, n), jnp.bool_, NamedSharding(mesh, P(None, "key"))),
    ).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < r * n * 8 * 4  # each chip holds a quarter
