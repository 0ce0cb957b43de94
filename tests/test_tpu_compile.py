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


def _walk_levels(c, sharding_of):
    return tuple(
        _spec((c >> j, 8), jnp.uint32, sharding_of(c >> j))
        for j in range(c.bit_length())
    )


@pytest.mark.parametrize("shards", [1, 4])
def test_root_walk_runs_the_node_kernel_on_v5e(topo, monkeypatch, shards):
    """The promotion-chain walk at the benchmark's capacity (2^19) hashes
    through the Pallas node kernel, one call per level and no loop; over a
    2x2 mesh, with the levels sharded, the kernel runs under shard_map."""
    from merklekv_tpu.merkle import incremental
    from merklekv_tpu.ops import sha256_pallas as sp

    # The dispatch reads the backend of this (CPU) process: steer it to
    # the chip's branch.
    monkeypatch.setenv("MKV_SHA256_BACKEND", "pallas")
    monkeypatch.setattr(sp, "pallas_supported", lambda: True)
    c = 1 << 19
    if shards == 1:
        mesh = None
        one = SingleDeviceSharding(topo.devices[0])
        levels = _walk_levels(c, lambda rows: one)
        n = _spec((), jnp.int32, one)
    else:
        mesh = Mesh(np.array(topo.devices[:shards]), ("key",))
        levels = _walk_levels(c, lambda rows: NamedSharding(
            mesh, P("key", None) if rows >= shards else P()))
        n = _spec((), jnp.int32, NamedSharding(mesh, P()))
    text = (
        incremental._ref_root_fn.__wrapped__(c, True, mesh)
        .lower(levels, n).compile().as_text()
    )
    assert text.count("tpu_custom_call") == c.bit_length() - 1
    assert " while(" not in text


@pytest.mark.parametrize("grow", [1, 2])
def test_restructure_compiles_for_v5e_at_the_benchmark_capacity(
        one_chip, monkeypatch, grow):
    """A structural batch's program at the benchmark's capacity (2^19),
    into the same capacity and into the doubled one, at the smallest
    fresh-digest bucket: the whole tree re-reduces through the Pallas node
    level kernel, one call per level."""
    from merklekv_tpu.merkle import incremental
    from merklekv_tpu.ops import sha256_pallas as sp

    monkeypatch.setenv("MKV_SHA256_BACKEND", "pallas")
    monkeypatch.setattr(sp, "pallas_supported", lambda: True)
    c = 1 << 19
    c_new = grow * c
    kb = incremental._restructure_buckets(c_new)[0]
    text = (
        incremental._restructure_fn.__wrapped__(c, c_new, kb, True)
        .lower(_spec((c, 8), jnp.uint32, one_chip),
               _spec((c_new,), jnp.int32, one_chip),
               _spec((kb,), jnp.int32, one_chip),
               _spec((kb, 8), jnp.uint32, one_chip))
        .compile().as_text()
    )
    assert text.count("tpu_custom_call") == c_new.bit_length() - 1
