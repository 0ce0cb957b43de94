"""Key index: mean time of one flush's key lookup and, for a structural
flush, its new key array and leaf positions (``span.device.key_index``,
both pumps)."""

from benchmark.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "device.key_index")
