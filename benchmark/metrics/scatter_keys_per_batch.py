"""Mirror pump: leaf digests scattered per pump batch over the traced
window (``device.scatter_keys`` / ``device.pump_batches``). The registry
is the process's, so both replicas' pumps count into it. Shows hot keys
coalescing."""

from benchmark.metrics._window import ratio


def read(ctx):
    return ratio(ctx, "metrics", "a", "device.scatter_keys",
                 "device.pump_batches")
