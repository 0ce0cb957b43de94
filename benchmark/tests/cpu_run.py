"""One benchmark run on the CPU at a tiny size, the look for a chip
skipped: ``python benchmark/tests/cpu_run.py <cell> <trace> [fault]``.
Prints the run's result line like ``run.py`` does.

Besides the cells of ``BENCHMARK.json`` it runs those of
``pending_cells.json``: cells whose files are in place but whose entries
wait for the chip (see PERF.md, section 7)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402

TINY = {
    "config": {"recordcount": 4096},
    "traffic": {"rate_ops_s": 600, "warm_bursts": [1, 16, 64],
                "warm_seconds": 1, "trace_lead_s": 0.5,
                "trace_seconds": 1.0, "settle_timeout_s": 10},
}
# Repair cells: 1,188 SETs an episode (600 ops/s x 0.033 x 60 s), two
# episodes, timeouts short enough for a control that never converges.
TINY_REPAIR = {
    "config": {"keycount": 4096},
    "traffic": {"rate_ops_s": 600, "warm_seconds": 1, "min_episodes": 2,
                "max_episodes": 3, "repair_timeout_s": 15, "grace_s": 10,
                "settle_timeout_s": 10},
}

_load_spec = spec.load_spec


def load_spec_with_pending(root: str = ROOT) -> dict:
    """``BENCHMARK.json`` with the pending cells' entries added."""
    bench = _load_spec(root)
    with open(os.path.join(root, "benchmark", "tests",
                           "pending_cells.json")) as f:
        pending = json.load(f)
    for key, entries in pending.items():
        have = {e["name"] for e in bench[key]}
        bench[key] += [e for e in entries if e["name"] not in have]
    return bench


def main() -> int:
    from benchmark import run
    from benchmark.faults import FAULTS

    spec.load_spec = load_spec_with_pending
    cell, trace = sys.argv[1], sys.argv[2]
    fault = FAULTS[sys.argv[3]]() if len(sys.argv) > 3 else None
    args = run.parse(["--workload", cell, "--seed", "3000000017",
                      "--seconds", "3", "--trace", trace])
    tiny = TINY_REPAIR if cell.startswith("etc_") else TINY
    return run.run_cell(args, require_tpu=False, faults=fault,
                        overrides=tiny)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
