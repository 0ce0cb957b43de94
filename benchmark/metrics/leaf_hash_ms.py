"""Kernels: mean time to hash one batch's leaves on the device and fetch
the digests (``span.device.leaf_hash``, both pumps)."""

from benchmark.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "device.leaf_hash")
