"""Mirror pump: mean time of one pump drain, staged changes to published
root (``span.pump.drain``, both pumps), the wait for the mirror's lock
included."""

from benchmark.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "pump.drain")
