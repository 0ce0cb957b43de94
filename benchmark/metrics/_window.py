"""Helpers the per-layer readers share: window deltas of program counters."""

from __future__ import annotations


def delta(ctx, source: str, node: str, name: str):
    """End minus start of one counter of one node over the traced run's
    window, or None where either edge lacks it."""
    a = ctx.readings.get(f"{source}.{node}.start", {}).get(name)
    b = ctx.readings.get(f"{source}.{node}.end", {}).get(name)
    if a is None or b is None:
        return None
    return b - a


def ratio(ctx, source: str, node: str, num: str, den: str):
    n, d = delta(ctx, source, node, num), delta(ctx, source, node, den)
    if n is None or not d:
        return None
    return n / d
