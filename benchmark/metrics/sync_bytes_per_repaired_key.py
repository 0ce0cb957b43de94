"""Anti-entropy: wire bytes a SYNC moves per key it repairs over the
traced window, ``(sync.bytes_sent + sync.bytes_received) /
anti_entropy.keys_repaired``: wire work against useful outcomes."""

from benchmark.metrics._window import delta


def read(ctx):
    sent = delta(ctx, "metrics", "a", "sync.bytes_sent")
    got = delta(ctx, "metrics", "a", "sync.bytes_received")
    keys = delta(ctx, "metrics", "a", "anti_entropy.keys_repaired")
    if sent is None or got is None or not keys:
        return None
    return (sent + got) / keys
