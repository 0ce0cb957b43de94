"""Structured tracing + metrics for the control plane.

The reference has no tracing at all — just env_logger text logs, with
structured tracing/Prometheus listed as an open roadmap issue
(/root/reference/README.md:1902-1906). Here observability is first-class;
the metrics core lives in ``merklekv_tpu/obs/`` (histograms, gauges, the
Prometheus exporter) and this module keeps the thin tracing API every
subsystem imports:

- ``span("name")`` context manager: wall-time spans aggregated into
  per-span counters/totals AND per-span latency histograms (obs.metrics),
  entered as a ``jax.profiler.TraceAnnotation`` of the same name for
  their duration (once the process has imported JAX: a profiler trace
  then carries every span on its host plane, on the device trace's
  clock, with no Python tracer), logged as one single-line JSON record
  through the ``merklekv`` logger when it is at INFO (stamped with the
  current anti-entropy cycle id when one is active, obs.trace, so a
  cycle's spans correlate in the log stream). Hot-path spans pass
  ``log=False``: they aggregate and annotate but never log;
- ``get_metrics()``: the process-wide obs registry (counters + spans +
  histograms + gauges) that subsystems bump; snapshot() for dashboards
  and the test suite;
- ``device_profile(logdir)``: wraps ``jax.profiler.trace`` so a TPU trace
  of the Merkle data plane is one ``with`` block (inspect with
  TensorBoard / xprof).
"""

from __future__ import annotations

import json
import logging
import sys
import time
from contextlib import contextmanager
from typing import Iterator, Optional

from merklekv_tpu.obs import tracewire
from merklekv_tpu.obs.metrics import Metrics, get_metrics
from merklekv_tpu.obs.trace import current_cycle_id

logger = logging.getLogger("merklekv")

__all__ = ["span", "Metrics", "get_metrics", "device_profile"]


_annotation = None  # jax.profiler.TraceAnnotation, once JAX is imported


def _trace_annotation(name: str):
    """A profiler host annotation for ``name``, or None in a process that
    has not imported JAX (it has no profiler to write to, and a span must
    not pay a JAX import)."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(name)


@contextmanager
def span(name: str, log: bool = True, **fields) -> Iterator[dict]:
    """Timed span; yields a dict callers may stuff result fields into.

    When a causal trace is active (obs/tracewire.py), the span also lands
    in the process-wide SpanCollector: it allocates a child span id and
    installs it for its duration, so nested spans — and traced wire
    requests issued inside — parent to it and the donor's serve spans
    stitch under this node's walk. ``log=False`` keeps the span out of
    the log stream whatever the logger's level."""
    extra: dict = {}
    tstate = tracewire.begin_span()
    annotation = _trace_annotation(name)
    if annotation is not None:
        annotation.__enter__()
    t0 = time.perf_counter()
    error: Optional[str] = None
    try:
        yield extra
    except BaseException as e:
        error = f"{type(e).__name__}: {e}"
        raise
    finally:
        dt = time.perf_counter() - t0
        if annotation is not None:
            annotation.__exit__(None, None, None)
        get_metrics().observe_span(name, dt)
        logged = log and logger.isEnabledFor(logging.INFO)
        cycle = (
            current_cycle_id() if logged or tstate is not None else None
        )
        if tstate is not None:
            tracewire.end_span(
                tstate, name, int(dt * 1e9), error=error, cycle=cycle or 0
            )
        if logged:
            record = {"span": name, "seconds": round(dt, 6), **fields,
                      **extra}
            if cycle is not None and "cycle" not in record:
                record["cycle"] = cycle
            if error is not None:
                record["error"] = error
            logger.info(json.dumps(record, default=str))


@contextmanager
def device_profile(logdir: str) -> Iterator[None]:
    """JAX profiler trace around a device workload (TensorBoard format)."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
