"""One benchmark run on the CPU at a tiny size, the look for a chip
skipped: ``python benchmark/tests/cpu_run.py <cell> <trace> [fault]``.
Prints the run's result line like ``run.py`` does."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TINY = {
    "config": {"recordcount": 4096},
    "traffic": {"rate_ops_s": 600, "warm_bursts": [1, 16, 64],
                "warm_seconds": 1, "trace_lead_s": 0.5,
                "trace_seconds": 1.0, "settle_timeout_s": 10},
}


def main() -> int:
    from benchmark import run
    from benchmark.faults import FAULTS

    cell, trace = sys.argv[1], sys.argv[2]
    fault = FAULTS[sys.argv[3]]() if len(sys.argv) > 3 else None
    args = run.parse(["--workload", cell, "--seed", "3000000017",
                      "--seconds", "3", "--trace", trace])
    return run.run_cell(args, require_tpu=False, faults=fault,
                        overrides=TINY)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
