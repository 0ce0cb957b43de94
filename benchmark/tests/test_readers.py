"""The per-layer readers of program spans and counters, on a synthetic
run context."""

from types import SimpleNamespace

import pytest

from benchmark import spec

START = {"span.mirror.stage_local.count": 100,
         "span.mirror.stage_local.total_us": 5_000,
         "span.pump.staged_age.count": 40,
         "span.pump.staged_age.total_us": 2_000_000,
         "span.pump.drain.count": 40,
         "span.pump.drain.total_us": 600_000,
         "span.device.leaf_hash.count": 40,
         "span.device.leaf_hash.total_us": 200_000,
         "span.device.root_walk.count": 40,
         "span.device.root_walk.total_us": 300_000,
         "device.dispatches": 1_000,
         "device.pump_batches": 40}
# Over the window: 50 stagings of 4.5 ms in all, 20 publishes.
END = {"span.mirror.stage_local.count": 150,
       "span.mirror.stage_local.total_us": 9_500,
       "span.pump.staged_age.count": 60,
       "span.pump.staged_age.total_us": 3_000_000,
       "span.pump.drain.count": 60,
       "span.pump.drain.total_us": 900_000,
       "span.device.leaf_hash.count": 60,
       "span.device.leaf_hash.total_us": 260_000,
       "span.device.root_walk.count": 60,
       "span.device.root_walk.total_us": 500_000,
       "device.dispatches": 1_100,
       "device.pump_batches": 60}
EXPECT = {"mirror_stage_ms": 0.09, "pump_staged_age_ms": 50.0,
          "pump_drain_ms": 15.0, "leaf_hash_ms": 3.0,
          "root_walk_ms": 10.0, "dispatches_per_publish": 5.0}


def _ctx(start=START, end=END):
    return SimpleNamespace(readings={"metrics.a.start": dict(start),
                                     "metrics.a.end": dict(end)})


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_span_and_counter_readers(name):
    got = spec.reader(name).read(_ctx())
    assert got == pytest.approx(EXPECT[name])
    # A program without the span or counter: nothing to read, no raise.
    assert spec.reader(name).read(_ctx(start={}, end={})) is None
