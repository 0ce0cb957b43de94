"""Anti-entropy: mean time of one SYNC's tree walk on the initiator
(``span.anti_entropy.walk``): the snapshot, the local tree rebuilt from
it, the TREELEVEL descent, the bounded leaf fetch and the repairs."""

from benchmark.metrics._span import mean_ms


def read(ctx):
    return mean_ms(ctx, "anti_entropy.walk")
