"""Knee sweep of an open-loop cell: one set-up, then a window at each
offered rate in turn, each printed as a JSON line (offered and achieved
rate, the end-to-end metrics, how late the generator ran, failures). The
cell's fixed rate is chosen from it once; the check never runs it.

    python3 benchmark/sweep.py --workload ycsb_a --seed 1 --seconds 10 \\
        --rates 2000,4000,8000
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run  # noqa: E402
from benchmark import spec as specmod  # noqa: E402


def sweep(argv=None, require_tpu: bool = True, overrides=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    a = p.parse_args(argv)
    rates = [float(r) for r in a.rates.split(",")]
    args = run.parse(["--workload", a.workload, "--seed", str(a.seed),
                      "--seconds", str(a.seconds)])
    args.rate = rates[0]
    spec = specmod.load_spec(_ROOT)
    cell = specmod.cell(spec, a.workload)
    config = specmod.config_of(spec, cell, _ROOT)
    traffic = specmod.traffic_of(cell, _ROOT)
    if overrides:
        from benchmark.nodes import deep_merge

        config = deep_merge(config, overrides.get("config", {}))
        traffic = deep_merge(traffic, overrides.get("traffic", {}))
    driver = specmod.driver(traffic, _ROOT)
    if run.open_devices(cell, require_tpu) is None:
        return 2
    from benchmark.nodes import Cluster

    ctx = run.Context(args, spec, cell, config, traffic)
    ctx.cluster = Cluster(config)
    try:
        for i, rate in enumerate(rates):
            t = time.monotonic()
            if i == 0:
                driver.setup(ctx)
            else:
                driver.absorb(ctx)
                driver.start_traffic(ctx, rate, i)
            w0 = time.time()
            e2e = driver.window(ctx)
            print(json.dumps({
                "rate": rate,
                "achieved_ops_s": ctx.readings["achieved_ops_s"],
                "attempted": ctx.attempted, "failed": ctx.failed,
                "late_p99_ms": ctx.readings["late_p99_ms"],
                **e2e, "segment_s": time.monotonic() - t,
                "window_wall": [w0, w0 + ctx.seconds],
                "phases": ctx.phases}), flush=True)
        driver.check(ctx)
    finally:
        ctx.cluster.close()
    print(json.dumps({"checks": {k: {"value": v, "limit": lim} for k, (v, lim)
                                 in ctx.checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    code = sweep()
    sys.stdout.flush()
    os._exit(code)
