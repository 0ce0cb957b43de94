"""Bench-regression gate: fail loudly when a round regresses >20%.

``BENCH_rNN.json`` records (committed per driver round) carry the headline
metric under ``parsed`` and one JSON line per side scenario in the stderr
``tail``. This tool extracts every scenario's primary metric from the two
newest rounds that produced usable numbers and exits 1 when any common
scenario regressed beyond the threshold — so a perf-eating change can't
ride a green CI into main.

Direction matters: throughput units (``keys/s``, ``events/s``,
``ops/s`` — e.g. the ``many_conn_throughput`` and ``overload_goodput``
scenarios) must not DROP; latency/size/overhead units (``ms``, ``us``,
``bytes``, ``%``) must not RISE. Rounds that crashed (rc != 0, no scenarios, null values)
are skipped rather than compared — a broken round is the driver's failure
signal, not a baseline; with fewer than two usable rounds the gate warns
and passes. Failed rounds carrying the structured ``error_kind`` verdict
(shared classifier, merklekv_tpu/utils/errorkind.py) are skipped WITH the
reason: ``environment`` reads as environment weather (a wedged backend
init), ``code`` as something to look at.

Usage: ``python tools/bench_gate.py [--dir .] [--threshold 0.2] [files..]``
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Optional

__all__ = ["extract_scenarios", "round_weather", "lower_is_better",
           "compare", "main"]


def extract_scenarios(record: dict) -> dict[str, dict]:
    """Scenario records ({'metric', 'value', 'unit', ...}) from one
    BENCH_rNN.json: the headline under ``parsed`` plus every JSON line in
    the stderr ``tail``. Truncated tail lines (the driver keeps only the
    last N bytes) and non-JSON chatter are skipped silently."""
    out: dict[str, dict] = {}
    tail = record.get("tail") or ""
    for line in tail.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            out[str(obj["metric"])] = obj
    parsed = record.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        out[str(parsed["metric"])] = parsed
    # Only scenarios with a usable number can gate.
    return {
        m: s
        for m, s in out.items()
        if isinstance(s.get("value"), (int, float)) and s["value"] > 0
    }


def round_weather(record: dict) -> Optional[str]:
    """The structured ``error_kind`` of a failed round, or None.

    bench.py classifies every whole-run failure through the shared
    environment|code table (merklekv_tpu/utils/errorkind.py) and stamps
    the verdict on the error record — a round that died in a wedged
    backend init then skips as ``environment`` WEATHER with
    the reason printed, instead of an anonymous "no usable scenarios".
    A ``code``-kind failure also skips (a broken round is never a
    baseline) but the verdict says someone should look at it."""
    for obj in ([record.get("parsed")] if isinstance(record.get("parsed"),
                                                    dict) else []) + [
        record
    ]:
        if obj.get("error") and obj.get("error_kind"):
            return str(obj["error_kind"])
    tail = record.get("tail") or ""
    # Newest-first: a round can emit several error records (an early
    # environment-kind backend-probe record, then a terminal code-kind
    # crash record from main()) — the TERMINAL verdict is the round's
    # verdict, so the last error_kind line wins.
    for line in reversed(tail.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and obj.get("error") and obj.get(
            "error_kind"
        ):
            return str(obj["error_kind"])
    return None


def lower_is_better(metric: str, unit: str) -> bool:
    """Regression direction for a scenario's primary metric."""
    u = (unit or "").lower()
    # Per-op cost units (allocs/op, copies/op) carry a "/..." that is NOT
    # a rate: check them before the throughput rule.
    if metric.endswith("_per_op") or "/op" in u:
        return True
    if "/s" in u:
        return False  # throughput: higher is better
    if metric.endswith(("_ms", "_us", "_pct", "_bytes")):
        return True
    return any(tok in u for tok in ("ms", "us", "byte", "%", "seconds"))


def compare(
    prev: dict[str, dict], cur: dict[str, dict], threshold: float = 0.20
) -> list[str]:
    """Human-readable regression lines for every common scenario whose
    primary metric moved past ``threshold`` in the bad direction."""
    regressions = []
    for metric in sorted(set(prev) & set(cur)):
        pv, cv = float(prev[metric]["value"]), float(cur[metric]["value"])
        unit = str(cur[metric].get("unit", ""))
        if lower_is_better(metric, unit):
            change = cv / pv - 1.0
            if change > threshold:
                regressions.append(
                    f"{metric}: {pv:g} -> {cv:g} {unit} "
                    f"(+{change * 100:.1f}%, lower is better)"
                )
        else:
            change = 1.0 - cv / pv
            if change > threshold:
                regressions.append(
                    f"{metric}: {pv:g} -> {cv:g} {unit} "
                    f"(-{change * 100:.1f}%, higher is better)"
                )
    return regressions


def _load(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"# {path}: unreadable ({e})", file=sys.stderr)
        return None


def main(argv: Optional[list[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_gate",
        description="compare the two newest usable BENCH_r*.json rounds "
        "and fail on >threshold regression in any scenario",
    )
    p.add_argument("files", nargs="*", help="explicit round files (sorted "
                   "oldest->newest); default: <dir>/BENCH_r*.json")
    p.add_argument("--dir", default=".", help="repo root to glob in")
    p.add_argument("--threshold", type=float, default=0.20)
    args = p.parse_args(argv)

    paths = args.files or sorted(
        glob.glob(os.path.join(args.dir, "BENCH_r*.json"))
    )
    usable: list[tuple[str, dict[str, dict]]] = []
    for path in paths:
        record = _load(path)
        if record is None:
            continue
        scenarios = extract_scenarios(record)
        if not scenarios:
            kind = round_weather(record)
            why = (
                f"error_kind={kind}"
                if kind
                else f"rc={record.get('rc')}"
            )
            tag = " as weather" if kind == "environment" else ""
            print(f"# {path}: no usable scenarios ({why}); skipped{tag}",
                  file=sys.stderr)
            continue
        usable.append((path, scenarios))
    if len(usable) < 2:
        print("bench gate: fewer than 2 usable rounds; nothing to compare")
        return 0
    (prev_path, prev), (cur_path, cur) = usable[-2], usable[-1]
    common = sorted(set(prev) & set(cur))
    print(f"bench gate: {prev_path} -> {cur_path}; "
          f"{len(common)} common scenarios "
          f"(threshold {args.threshold * 100:.0f}%)")
    regressions = compare(prev, cur, args.threshold)
    for line in regressions:
        print(f"REGRESSION {line}")
    if regressions:
        print(f"bench gate: FAILED ({len(regressions)} regression(s))")
        return 1
    print("bench gate: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
