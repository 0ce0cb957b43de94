"""Mirror pump: structural flushes per pump publish over the traced window
(``device.structural_batches`` / ``device.pump_batches``, both pumps).
Near 1 where nearly every publish carries an insert."""

from benchmark.metrics._window import ratio


def read(ctx):
    return ratio(ctx, "metrics", "a", "device.structural_batches",
                 "device.pump_batches")
