"""Run one benchmark cell on the machine this starts on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics come from
``BENCHMARK.json`` and the files it names (``benchmark/spec.py``). A run
generates its data from ``--seed``, starts the configuration's nodes
through the node's entry point, loads and warms them and the cell's
shapes (all of that is ``setup_s``), measures for ``--seconds``, then
checks every answer against the plain reference (``benchmark/reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: every number compared, with its limit.
The same numbers end standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

_T_START = time.monotonic()
_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import spec as specmod  # noqa: E402

WATCHDOG_S = 1150.0  # a hung run ends non-zero; a first run may compile
OUT = os.path.join(_HERE, "out")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """XLA programs made ready in this process, from ``jax.monitoring``:
    ``total`` seconds (summed over threads) and ``count`` programs, each
    compiled or loaded from the persistent cache (JAX times both as one
    backend compile), ``hits`` of them loaded; and, from JAX's own log,
    the name and argument shapes of every program lowered (``lowered``:
    monotonic time, text)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import logging

        import jax

        self._mu = threading.Lock()
        self.total = 0.0
        self.count = 0
        self.hits = 0
        self.lowered: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)
        clock = self

        class _Lowerings(logging.Handler):
            def emit(self, record) -> None:
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    with clock._mu:
                        clock.lowered.append((time.monotonic(), msg))

        log_ = logging.getLogger("jax._src.interpreters.pxla")
        log_.setLevel(logging.DEBUG)
        log_.addHandler(_Lowerings())
        log_.propagate = False

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self._EVENT:
            with self._mu:
                self.total += duration
                self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            with self._mu:
                self.hits += 1

    def since(self, t: float) -> list[str]:
        """What was lowered after monotonic ``t``: the program's name and
        the last of its argument shapes (the batch's, for a scatter)."""
        with self._mu:
            return [m.split(". Argument mapping")[0][-240:]
                    for at, m in self.lowered if at >= t]


class Context:
    """What one run knows; drivers fill it, per-layer readers read it."""

    def __init__(self, args, spec: dict, cell: dict, config: dict,
                 traffic: dict) -> None:
        self.args, self.spec, self.cell = args, spec, cell
        self.config, self.traffic = config, traffic
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.phases: dict = {}
        self.readings: dict = {}
        self.checks: dict = {}  # name -> (value, limit)
        self.attempted = 0
        self.failed = 0
        self.t0 = None  # monotonic start of the window
        self.trace_window = None  # (start, end) monotonic, traced runs
        self.trace_summary = None
        self.device_kind = None
        self.cluster = None

    def check(self, name: str, value, limit) -> None:
        self.checks[name] = (value, limit)


def _tracer(ctx: Context, trace_dir: str, done: threading.Event) -> None:
    import jax

    lead = float(ctx.traffic.get("trace_lead_s", 1.0))
    length = float(ctx.traffic.get("trace_seconds", 3.0))
    delay = ctx.t0 + lead - time.monotonic()
    if delay > 0 and done.wait(delay):
        return
    # No Python tracer: at the cells' load it slows every thread of the
    # node and the device plane can go missing from the trace; the
    # benchmark's own annotations name what the host did.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_a = time.monotonic()
    done.wait(length)
    t_b = time.monotonic()
    jax.profiler.stop_trace()
    ctx.trace_window = (t_a, t_b)


def open_devices(cell: dict, require_tpu: bool):
    """The local devices, or None (and why on stderr) where there is no
    TPU or fewer chips than the cell asks for. JAX's persistent compile
    cache goes to a fixed directory of the checkout unless the environment
    names one."""
    if require_tpu:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(_ROOT, ".jax_cache"))
        # Every program, however quick to compile, so that a run after the
        # first in a checkout compiles nothing.
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"benchmark: no accelerator: {e}")
        return None
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < int(cell["chips"])):
        log(f"benchmark: needs {cell['chips']} TPU chip(s), found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return None
    return devices


def run_cell(args, require_tpu: bool = True, faults=None,
             overrides: dict | None = None) -> int:
    """One run. The keyword arguments are for the harness's own tests on
    the CPU: skip the look for a chip, break the timed path (``faults``),
    and shrink the configuration or traffic (``overrides``)."""
    from benchmark.nodes import deep_merge

    spec = specmod.load_spec(_ROOT)
    cell = specmod.cell(spec, args.workload)
    config = specmod.config_of(spec, cell, _ROOT)
    traffic = specmod.traffic_of(cell, _ROOT)
    if overrides:
        config = deep_merge(config, overrides.get("config", {}))
        traffic = deep_merge(traffic, overrides.get("traffic", {}))
    driver = specmod.driver(traffic, _ROOT)
    devices = open_devices(cell, require_tpu)
    if devices is None:
        return 2
    import jax

    ctx = Context(args, spec, cell, config, traffic)
    ctx.device_kind = devices[0].device_kind
    clock = CompileClock()
    os.makedirs(OUT, exist_ok=True)
    trace_dir = os.path.join(OUT, "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    from benchmark.nodes import Cluster

    t = time.monotonic()
    ctx.phases["start_s"] = t - _T_START
    ctx.cluster = Cluster(config)
    ctx.phases["nodes_s"] = time.monotonic() - t
    try:
        driver.setup(ctx)  # returns at the window's start, ctx.t0
        setup_s = ctx.t0 - _T_START
        if faults is not None and hasattr(faults, "before_window"):
            faults.before_window(ctx)
        compile_0, count_0, hits_0 = clock.total, clock.count, clock.hits
        ctx.phases["setup_compile_s"] = compile_0
        ctx.readings.update(setup_programs=count_0, setup_cache_hits=hits_0)
        done = threading.Event()
        tracer = None
        # A driver that traces a span of its own choosing (one repair
        # episode) says so; the others get a fixed slice of the window.
        if ctx.trace and not getattr(driver, "TRACES_ITSELF", False):
            tracer = threading.Thread(target=_tracer,
                                      args=(ctx, trace_dir, done))
            tracer.start()
        try:
            e2e = driver.window(ctx)
        finally:
            done.set()
            if tracer is not None:
                tracer.join()
        ctx.readings["window_compile_s"] = clock.total - compile_0
        ctx.readings["window_programs"] = clock.count - count_0
        ctx.readings["window_cache_hits"] = clock.hits - hits_0
        ctx.readings["window_lowered"] = clock.since(ctx.t0)
        for m in ctx.readings["window_lowered"][:20]:
            log(f"lowered in the window: {m}")
        # Set-up made every program the window needs ready: one made in
        # the window stalls the path that waits for it.
        ctx.check("programs_readied_in_window",
                  ctx.readings["window_programs"], 0)
        # What the window measured besides the cell's metrics, for the
        # record.
        ctx.readings.update({f"e2e.{k}": v for k, v in e2e.items()})
        peak = 0
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        if ctx.trace and ctx.trace_window is not None:
            from benchmark.trace import read_dir

            ctx.trace_summary = read_dir(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        if ctx.trace:
            for m in specmod.per_layer_of(spec, cell["name"]):
                v = specmod.reader(m["name"], _ROOT).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in specmod.end_to_end_of(spec, cell["name"]):
                v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if faults is not None and hasattr(faults, "before_check"):
            faults.before_check(ctx)
        driver.check(ctx)
    finally:
        t = time.monotonic()
        gen = ctx.readings.get("gen")
        if gen is not None:
            gen.close()  # load workers of a run that failed part-way
        ctx.cluster.close()
        ctx.phases["close_s"] = time.monotonic() - t

    device = {"platform": devices[0].platform, "kind": ctx.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(v <= lim for v, lim in ctx.checks.values())
              and bool(ctx.checks),
              "attempted": ctx.attempted, "failed": ctx.failed,
              "metrics": metrics, "device": device}
    s = ctx.trace_summary
    if ctx.trace and s is not None and s.devices:
        device["busy_s"] = s.busy_ns / 1e9
        device["window_s"] = ctx.trace_window[1] - ctx.trace_window[0]
        result["breakdown"] = {
            "device_ops": [[n, t / 1e9] for n, t in s.op_kinds()[:10]],
            "idle_gaps": [[n, t / 1e9] for t, n in s.gaps[:10]],
        }
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in ctx.checks.items()}
    record = dict(result, cell=cell["name"], seed=ctx.seed,
                  seconds=ctx.seconds, trace=ctx.trace, phases=ctx.phases,
                  setup_s=setup_s,
                  readings={k: v for k, v in ctx.readings.items()
                            if isinstance(v, (int, float, str))},
                  window_lowered=ctx.readings.get("window_lowered", []))
    if s is not None:
        # What the trace held, for whoever next writes a reader of it.
        record["trace_planes"] = s.planes
        record["trace_top_ops"] = [
            [n, t / 1e9, list(s.op_labels.get(n, ()))[:4]]
            for n, t in sorted(s.op_ns.items(), key=lambda kv: -kv[1])[:40]]
    with open(os.path.join(OUT, f"last_{cell['name']}.json"), "w") as f:
        json.dump(record, f, indent=1)
    log("phases: " + " ".join(f"{k}={v:.3f}" for k, v in ctx.phases.items()))
    log(f"setup_s={setup_s:.3f} attempted={ctx.attempted} "
        f"failed={ctx.failed}")
    for k, (v, lim) in ctx.checks.items():
        log(f"check {k}: {v} limit {lim}")
    print(json.dumps(result), flush=True)
    return 0


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--fault", default=None,
                   help="plant a fault (benchmark/faults.py) to see the "
                        "comparison fail: the controls; never in a "
                        "measured run")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    timer = threading.Timer(WATCHDOG_S, lambda: (
        log(f"benchmark: watchdog {WATCHDOG_S:.0f}s exceeded"),
        os._exit(3)))
    timer.daemon = True
    timer.start()
    try:
        faults = None
        if args.fault:
            from benchmark.faults import FAULTS

            faults = FAULTS[args.fault]()
        return run_cell(args, faults=faults)
    except Exception as e:
        import traceback

        traceback.print_exc()
        log(f"benchmark: FAILED: {type(e).__name__}: {e}")
        return 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Daemon threads of the stopped nodes must not hold the exit.
    os._exit(code)
