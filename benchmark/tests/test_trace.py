"""The trace reduction on a small synthetic trace."""

from jax.profiler import ProfileData

from benchmark.trace import reduce_profile

# Device ops (ns): leaf 0-2000, fusion 1500-3000 (overlaps), leaf
# 6000-7000; busy = 3000 + 1000 = 4000 of the span 0-7000. A host
# annotation covers the gap 3000-6000.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 7000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "sha256_leaf" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.7" } }
  event_metadata { key: 3 value { id: 3 name: "jit_go" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "benchmark.sync" } }
}
"""


def _profile():
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(TRACE))


def test_busy_kernel_and_gaps():
    s = reduce_profile(_profile())
    assert s.devices == 1
    assert s.busy_ns == 4000
    assert s.kernel_s("sha256_leaf") == 3000 / 1e9
    assert s.op_ns["fusion.7"] == 1500
    assert s.gaps == [(3000, "benchmark.sync")]
    assert "XLA Ops" in s.planes["/device:TPU:0"]
