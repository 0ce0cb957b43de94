"""Structural flush: mean time of one flush that inserts or deletes keys
(``span.device.restructure``, both pumps): key lookup and new key array,
leaf hash of the fresh records, and the enqueue of the program that
gathers the shifted leaves and re-reduces the whole tree."""

from benchmark.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "device.restructure")
