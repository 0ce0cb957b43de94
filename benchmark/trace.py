"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
over the devices traced; the idle share is one less busy over the traced
window. Kernel time is the summed device duration of the operations whose
name, or any string stat, names the kernel. Each long idle gap is named
after the host event that overlaps it most (``TraceAnnotation`` spans of
the benchmark, and the runtime's own host events).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

_DEVICE_PREFIX = "/device:TPU:"
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"


@dataclass
class TraceSummary:
    devices: int = 0
    busy_ns: float = 0.0  # averaged over devices
    op_ns: dict = field(default_factory=dict)  # op name -> total ns
    op_labels: dict = field(default_factory=dict)  # op name -> stat strings
    gaps: list = field(default_factory=list)  # (ns, host name)
    planes: dict = field(default_factory=dict)  # plane -> line names

    def op_kinds(self) -> list[tuple[str, float]]:
        """(kind, ns) by total time: an op's kind is its HLO instruction
        name without the ``%`` and the numeric suffix (``%while.30 = ...``
        is ``while``, ``%sha256_leaf.2 = ...`` is ``sha256_leaf``)."""
        out: dict[str, float] = {}
        for name, t in self.op_ns.items():
            kind = re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))
            out[kind] = out.get(kind, 0.0) + t
        return sorted(out.items(), key=lambda kv: -kv[1])

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of every op that names ``kernel``."""
        ns = 0.0
        for name, t in self.op_ns.items():
            if kernel in name or any(kernel in s for s in
                                     self.op_labels.get(name, ())):
                ns += t
        return ns / 1e9


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _string_stats(event) -> tuple:
    out = []
    try:
        for _name, value in event.stats:
            if isinstance(value, str):
                out.append(value)
    except Exception:  # a stat the bindings cannot convert
        pass
    return tuple(out)


def reduce_profile(profile, gaps: int = 10) -> TraceSummary:
    """``profile`` is a ``jax.profiler.ProfileData``. Gaps are searched
    over the span of the host's events (the traced window)."""
    summ = TraceSummary()
    host_events: list[tuple[float, float, str]] = []
    busy_total = 0.0
    merged_first: list[tuple[float, float]] | None = None
    for plane in profile.planes:
        summ.planes[plane.name] = [line.name for line in plane.lines]
        if plane.name == _HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host_events.append((ev.start_ns, ev.end_ns, ev.name))
            continue
        if not plane.name.startswith(_DEVICE_PREFIX):
            continue
        rest = plane.name[len(_DEVICE_PREFIX):]
        if not rest.isdigit():
            continue
        ivs = []
        for line in plane.lines:
            if line.name != _OPS_LINE:
                continue
            for ev in line.events:
                ivs.append((ev.start_ns, ev.end_ns))
                summ.op_ns[ev.name] = summ.op_ns.get(ev.name, 0.0) \
                    + ev.duration_ns
                if ev.name not in summ.op_labels:
                    summ.op_labels[ev.name] = _string_stats(ev)
        merged = _merge(ivs)
        busy_total += sum(e - s for s, e in merged)
        summ.devices += 1
        if merged_first is None:
            merged_first = merged
    if summ.devices:
        summ.busy_ns = busy_total / summ.devices
        for name in summ.op_ns:
            summ.op_ns[name] /= summ.devices
    if merged_first:
        if host_events:  # the host's events span the traced window
            lo = min(e[0] for e in host_events)
            hi = max(e[1] for e in host_events)
        else:
            lo, hi = merged_first[0][0], merged_first[-1][1]
        edges = [lo] + [x for iv in merged_first for x in iv] + [hi]
        holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        holes.sort(key=lambda h: h[0] - h[1])
        host_events.sort()
        for s, e in holes[:gaps]:
            summ.gaps.append((e - s, _host_name(host_events, s, e)))
    return summ


def _host_name(events, s: float, e: float) -> str:
    """What the host was doing in the gap [s, e): the benchmark's own
    annotation that overlaps it most, else the shortest host event that
    covers most of it (the innermost frame, with Python tracing on)."""
    best, name = 0.0, None
    inner, inner_len = None, float("inf")
    for hs, he, hn in events:
        if hs >= e:
            break
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        if hn.startswith("benchmark.") and ov > best:
            best, name = ov, hn
        if ov >= 0.5 * (e - s) and he - hs < inner_len:
            inner, inner_len = hn, he - hs
    return name or inner or "no_host_event"


def read_dir(trace_dir: str) -> TraceSummary | None:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    return reduce_profile(ProfileData.from_file(files[-1]))
