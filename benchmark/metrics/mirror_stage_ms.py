"""Replication drain: mean time to stage one drained batch of node a's
local writes into its device mirror (``span.mirror.stage_local``), the
wait for the mirror's lock included."""

from benchmark.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "mirror.stage_local")
