"""Mirror pump: mean age of the oldest staged change at each publish
(``span.pump.staged_age``, both pumps): the in-program part of tree lag."""

from benchmark.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "pump.staged_age")
