"""Dispatch guard: device program calls per pump publish over the traced
window (``device.dispatches`` / ``device.pump_batches``, both nodes)."""

from benchmark.metrics._window import ratio


def read(ctx):
    return ratio(ctx, "metrics", "a", "device.dispatches",
                 "device.pump_batches")
