"""Helpers the span readers share: the mean of one program span over the
traced run's window, from the ``span.<name>.count`` / ``.total_us`` lines
of node a's METRICS (the process's registry: every node's spans)."""

from __future__ import annotations

from benchmark.metrics._window import ratio


def mean_ms(ctx, name: str):
    """Mean duration in ms of span ``name`` over the window, or None where
    the program records no such span (a program that predates it)."""
    us = ratio(ctx, "metrics", "a", f"span.{name}.total_us",
               f"span.{name}.count")
    return None if us is None else us / 1000.0
