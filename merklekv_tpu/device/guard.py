"""Deadline-guarded device dispatch: the pump thread can never hang.

Every device program call in the serving stack (tree build / incremental
scatter / restructure / level gathers / the sharded N-replica diff) routes
through :func:`DispatchGuard.run` instead of touching jax directly:

- the call executes on its **own daemon guard thread** with a
  ``[device] dispatch_deadline_ms`` bound — a dispatch wedged inside the
  runtime is ABANDONED at the deadline (the thread is orphaned), so the
  caller gets a typed :class:`DispatchHungError` instead of blocking
  forever, and concurrent dispatches never queue behind each other's
  deadlines;
- :meth:`DispatchGuard.call` compiles a jitted program AHEAD of that
  deadline (:meth:`DispatchGuard.compiled`), so a cold compile is never
  read as a hang;
- failures are classified by the shared environment|code table
  (``merklekv_tpu.utils.errorkind``): environment-classified errors
  (transient runtime or connection blip) retry ONCE under
  ``retry.DEVICE_DISPATCH`` backoff; code errors raise immediately;
- everything that escapes wraps as :class:`DeviceDispatchError` carrying
  the classified ``kind`` — the degradation ladder's input signal.

Chaos seam: :func:`set_inject` installs a fault injector
(``testing/device_faults.DeviceFaultInjector``) whose hooks run INSIDE the
guarded call — fail-Nth, persistent-until-heal, hang-past-deadline,
corrupt-result — mirroring the WAL's ``WalErrnoInjector``. Spawned server
processes pick injection up from the ``MKV_DEVICE_FAULTS`` env var (the
process-level chaos hook for the CI device-chaos step). Nothing here
imports jax at module load; the guard is pure threading and costs one
thread spawn per dispatch (~0.1 ms, small against the dispatch itself).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional, TypeVar

from merklekv_tpu.cluster.retry import DEVICE_DISPATCH, RetryPolicy
from merklekv_tpu.obs.metrics import get_metrics
from merklekv_tpu.utils.errorkind import CODE, ENVIRONMENT, classify_exception
from merklekv_tpu.utils.tracing import span

__all__ = [
    "DeviceDispatchError",
    "DispatchHungError",
    "DispatchGuard",
    "get_guard",
    "configure",
    "set_inject",
    "get_inject",
]

T = TypeVar("T")

# Guarded calls running on this thread: a call made inside another one
# (inline or on its guard thread) is part of it, not a dispatch of its own.
_active = threading.local()


class DeviceDispatchError(RuntimeError):
    """A guarded device program call failed past its retry budget.

    ``kind`` is the shared classifier's verdict (``environment`` | ``code``)
    and ``label`` names the dispatch seam (``build`` / ``scatter`` /
    ``restructure`` / ``levels`` / ``diff`` — ``shard_``-prefixed on the
    sharded backend), so the degradation ladder and the flight timeline
    both know WHAT failed and WHY without re-parsing tracebacks."""

    def __init__(self, label: str, kind: str, cause: str) -> None:
        super().__init__(f"device dispatch {label!r} failed ({kind}): {cause}")
        self.label = label
        self.kind = kind
        self.cause = cause


class DispatchHungError(DeviceDispatchError):
    """A guarded dispatch blew through the deadline and was abandoned.
    Always ``environment``: a hang is runtime weather, and the
    wedged worker thread may still be inside the backend — the guard
    replaced it rather than wait."""

    def __init__(self, label: str, deadline_ms: float) -> None:
        DeviceDispatchError.__init__(
            self, label, ENVIRONMENT,
            f"dispatch deadline {deadline_ms:g}ms expired; dispatch "
            "abandoned",
        )
        self.deadline_ms = deadline_ms


class DispatchGuard:
    """Deadline + classify + retry-once wrapper for device program calls.

    ``deadline_ms <= 0`` disables the executor round-trip (calls run
    inline, unbounded — the pre-guard behavior); classification, retry,
    and the chaos seam still apply.
    """

    def __init__(
        self,
        deadline_ms: float = 60_000.0,
        policy: RetryPolicy = DEVICE_DISPATCH,
    ) -> None:
        self._deadline_ms = float(deadline_ms)
        self._policy = policy
        self._executables: dict = {}  # compiled() cache, per signature

    @property
    def deadline_ms(self) -> float:
        return self._deadline_ms

    def set_deadline_ms(self, deadline_ms: float) -> None:
        self._deadline_ms = float(deadline_ms)

    # -- execution -----------------------------------------------------------
    def _bounded(self, label: str, fn: Callable[[], T]) -> T:
        """One guarded attempt: run ``fn`` on a fresh daemon thread under
        the deadline; abandon the thread on a blow-through.

        One thread PER CALL, deliberately: a shared worker would make the
        deadline measure queue-wait + execution, so a dispatch queued
        behind a legitimate slow compile would be falsely classified as
        hung without ever running — and it would serialize every device
        dispatch in the process. Per-call threads cost ~0.1 ms each,
        small against a device dispatch, and pump coalescing bounds the
        rate. Plain daemon threads instead of concurrent.futures: an
        abandoned wedged thread must not block interpreter exit (TPE
        joins its workers atexit)."""
        deadline_ms = self._deadline_ms
        if (
            deadline_ms <= 0
            or threading.current_thread().name == "mkv-dispatch-guard"
        ):
            # Disabled, or already ON a guard thread (a nested guarded
            # call — e.g. a query-path level gather triggering a staged
            # flush): run inline rather than stacking guard threads.
            return fn()
        box: list = []
        done = threading.Event()

        def run() -> None:
            try:
                box.append((True, fn()))
            except BaseException as e:  # delivered to the caller
                box.append((False, e))
            done.set()

        threading.Thread(
            target=run, daemon=True, name="mkv-dispatch-guard"
        ).start()
        if not done.wait(timeout=deadline_ms / 1000.0):
            # Wedged: orphan the thread (daemon — it may never return).
            # It still holds whatever backend handle it blocked in; that
            # is exactly why its result, if it ever arrives, is discarded.
            get_metrics().inc("device.guard_timeouts")
            raise DispatchHungError(label, deadline_ms)
        ok, out = box[0]
        if ok:
            return out
        raise out

    def compiled(self, label: str, program, *args):
        """The executable of the jitted ``program`` for ``args``' signature
        (shapes, dtypes, shardings), compiled on the CALLER's thread outside
        the deadline and cached. A cold compile of a full-size program takes
        seconds to tens of seconds (a 2^20-leaf tree build compiles in ~20 s
        for v5e); the deadline then bounds only execution, so a first
        compile is never read as a hang. A compile error raises a
        ``DeviceDispatchError`` classified like any dispatch failure."""
        import jax

        leaves, tree = jax.tree_util.tree_flatten(args)
        key = (program, tree, tuple(
            (x.shape, str(x.dtype), getattr(x, "sharding", None))
            for x in leaves
        ))
        exe = self._executables.get(key)
        if exe is None:
            try:
                with span("device.compile", label=label):
                    exe = program.lower(*args).compile()
            except Exception as e:
                get_metrics().inc("device.guard_errors")
                raise DeviceDispatchError(
                    label, classify_exception(e),
                    f"compile: {type(e).__name__}: {e}",
                ) from e
            self._executables[key] = exe
        return exe

    def call(self, label: str, program, *args, fetch: bool = False):
        """Run the jitted ``program`` on ``args`` under the guard, compiled
        ahead of the deadline (:meth:`compiled`). The result stays on the
        device (dispatch is asynchronous); ``fetch`` brings it to the host
        inside the deadline instead, so a wedged execution is caught."""
        import jax

        exe = self.compiled(label, program, *args)
        if fetch:
            return self.run(label, lambda: jax.device_get(exe(*args)))
        return self.run(label, lambda: exe(*args))

    def run(self, label: str, fn: Callable[[], T]) -> T:
        """Run one device program call under the guard. Returns ``fn()``'s
        value, or raises :class:`DeviceDispatchError` (classified) /
        :class:`DispatchHungError` (abandoned). Each call that is not
        nested in another counts once in ``device.dispatches``."""
        if not getattr(_active, "depth", 0):
            get_metrics().inc("device.dispatches")

        def guarded() -> T:
            _active.depth = getattr(_active, "depth", 0) + 1
            try:
                return fn()
            finally:
                _active.depth -= 1

        inject = get_inject()
        if inject is not None:
            call = lambda: inject.around(label, guarded)  # noqa: E731
        else:
            call = guarded
        retried = False
        while True:
            try:
                return self._bounded(label, call)
            except DispatchHungError:
                raise  # never retried: the stall budget IS the deadline
            except DeviceDispatchError:
                raise  # already classified by a nested guarded call
            except BaseException as e:
                if isinstance(e, (KeyboardInterrupt, SystemExit)):
                    raise
                kind = classify_exception(e)
                if kind == ENVIRONMENT and not retried:
                    retried = True
                    get_metrics().inc("device.guard_retries")
                    time.sleep(self._policy.backoff(0))
                    continue
                get_metrics().inc("device.guard_errors")
                raise DeviceDispatchError(
                    label, kind, f"{type(e).__name__}: {e}"
                ) from e


# -- module seam (one guard per process, one injection slot) ----------------

_guard = DispatchGuard()
_inject = None
_env_checked = False


def get_guard() -> DispatchGuard:
    return _guard


def configure(deadline_ms: Optional[float] = None) -> DispatchGuard:
    """Process-wide guard configuration (node startup). Multiple in-process
    nodes share the guard; last configuration wins (documented)."""
    if deadline_ms is not None:
        _guard.set_deadline_ms(deadline_ms)
    return _guard


def set_inject(inj) -> None:
    """Install (or, with None, remove) the chaos injector. The injector
    must expose ``around(label, fn) -> result``."""
    global _inject, _env_checked
    _inject = inj
    _env_checked = True  # explicit installation overrides the env hook


def get_inject():
    """The active injector, installing the ``MKV_DEVICE_FAULTS`` env-var
    injector on first use in a spawned process (CI chaos step)."""
    global _inject, _env_checked
    if not _env_checked:
        _env_checked = True
        spec = os.environ.get("MKV_DEVICE_FAULTS", "")
        if spec:
            from merklekv_tpu.testing.device_faults import (
                DeviceFaultInjector,
            )

            _inject = DeviceFaultInjector.from_spec(spec).install()
    return _inject
