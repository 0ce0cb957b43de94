"""Kernels: mean time of one publish's root walk, a blocking fetch that
also waits for the scatter enqueued before it (``span.device.root_walk``,
both pumps)."""

from benchmark.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "device.root_walk")
