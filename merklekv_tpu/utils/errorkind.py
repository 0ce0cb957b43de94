"""Shared environment-vs-code failure classifier.

One regex table, three consumers. The multichip probe grew the original
``_classify_error`` (``__graft_entry__``) because a "need 8 devices, have
1" shortfall was indistinguishable from a code regression; the same
two-way split turned out to be exactly what the device dispatch guard
(``merklekv_tpu.device.guard``) needs to decide retry-vs-raise, and what
``bench.py``'s backend probe needs so a failed bench round (a wedged
backend init) lands as structured weather ``bench_gate`` can skip
instead of baselining. Promoting the table here keeps the three classifiers
from drifting apart.

Semantics:

- ``"environment"`` — device-complement shortfalls, backend init
  failures, deadlines/watchdogs, dead RPC channels. The DRIVER's weather:
  transient or out of this code's control. The guard retries these once;
  triage must not page on them.
- ``"code"`` — everything else (shape errors, assertion failures, bugs).
  Never retried, always pages.
"""

from __future__ import annotations

import re

__all__ = ["ENVIRONMENT", "CODE", "classify_error", "classify_exception"]

ENVIRONMENT = "environment"
CODE = "code"

# Matched case-insensitively against the stringified failure. Grouped by the
# failure family they fingerprint; extend here (never locally) so the probe,
# the guard, and the bench probe stay in agreement.
_ENV_ERROR_PATTERNS = (
    # Device-complement shortfalls ("need 8 devices, have 1").
    r"need \d+ devices",
    r"mesh needs \d+ devices",
    r"devices, have \d+",
    r"no devices? (?:found|available)",
    # Backend initialization trouble.
    r"unable to initialize backend",
    r"backend '\w+' requested, but it failed",
    r"failed to connect",
    r"tpu.*(?:unavailable|not found|already in use)",
    # Deadlines and watchdogs: a hang is backend weather, not a
    # regression (a watchdog's rc=124; the dispatch guard's abandonment).
    # "timed out" (socket.timeout's str), NOT "timeout": a message merely
    # MENTIONING a timeout parameter must not read as weather. And no
    # "resource exhausted": XLA RESOURCE_EXHAUSTED is an OOM — a sizing
    # regression that should page, not retry.
    r"deadline.?exceeded",
    r"watchdog: .* deadline expired",
    r"dispatch deadline",
    r"timed out",
    # Dead RPC channels mid-program (the backend died under us).
    r"socket closed",
    r"connection reset",
    r"broken pipe",
)

_ENV_RE = re.compile("|".join(f"(?:{p})" for p in _ENV_ERROR_PATTERNS))


def classify_error(message: str) -> str:
    """``"environment"`` for device/backend shortfalls, ``"code"``
    for everything else."""
    return ENVIRONMENT if _ENV_RE.search(str(message).lower()) else CODE


def classify_exception(exc: BaseException) -> str:
    """Classify an exception by its message AND type. ``OSError``/
    ``ConnectionError`` and friends are environment by construction even
    when their message matches no pattern (errno text varies by libc)."""
    if isinstance(exc, (OSError, ConnectionError, TimeoutError)):
        return ENVIRONMENT
    return classify_error(f"{type(exc).__name__}: {exc}")
