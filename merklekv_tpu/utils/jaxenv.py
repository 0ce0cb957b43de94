"""JAX process environment: compile cache, backend probe, device failure.

Platform selection is plain ``JAX_PLATFORMS``: tests and the server
processes they spawn run with ``JAX_PLATFORMS=cpu`` in their environment;
a node on a TPU host uses the chip by default.
"""

from __future__ import annotations

import os
import threading
import warnings

__all__ = [
    "configure_compile_cache",
    "note_device_failure",
    "device_failed",
    "probe_default_backend",
]

# The fixed in-checkout cache directory used when JAX_COMPILATION_CACHE_DIR
# is unset (git-ignored). A fixed path, never one built from a temp name, a
# pid or the time: a cache that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
_cache_configured = False


def configure_compile_cache() -> "str | None":
    """Place JAX's persistent compilation cache; call before the first
    device use. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing is set here. Otherwise, on an accelerator, the cache
    goes to :data:`COMPILE_CACHE_DIR`; the CPU backend (tests) gets none —
    its compiles are cheap, and its cached executables are tied to the
    compiling host. Idempotent; returns the directory in use, or None."""
    global _cache_configured
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.default_backend() == "cpu":
        return None
    if not _cache_configured:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
        _cache_configured = True
    return COMPILE_CACHE_DIR


def probe_default_backend(timeout: float = 90.0) -> "str | None":
    """Resolve ``jax.default_backend()`` in a THROWAWAY subprocess, bounded.

    Backend init against an absent or already-claimed TPU can raise — or
    hang past any useful deadline — and once the parent process has tried
    and failed, ``jax_platforms`` may be frozen mid-init with no recourse.
    Probing in a child keeps the parent's jax import pristine: on None
    (probe crashed or timed out), callers pin the parent to CPU *before*
    its first jax import.
    """
    import subprocess
    import sys

    # Environment already pins a non-TPU platform (the test tier, spawned
    # server processes): the answer is forced, skip the throwaway child.
    pinned = os.environ.get("JAX_PLATFORMS")
    if pinned and "tpu" not in pinned:
        return pinned.split(",")[0]

    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except (subprocess.TimeoutExpired, OSError):
        return None
    if out.returncode != 0:
        return None
    name = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
    return name or None


# -- graceful device degradation --------------------------------------------
#
# TPU/Pallas init can fail at runtime (chip already claimed by another
# process, runtime trouble). Serving paths must not
# turn that into a crash loop: the first failure is recorded here, a single
# warning is emitted, and every device-vs-CPU dispatch point checks
# ``device_failed()`` to pin itself to the host path from then on.

_device_mu = threading.Lock()
_device_fallback = False


def note_device_failure(err: BaseException, what: str = "device path") -> None:
    """Record a device-path failure; warn exactly once process-wide."""
    global _device_fallback
    with _device_mu:
        first = not _device_fallback
        _device_fallback = True
    if first:
        warnings.warn(
            f"JAX {what} unavailable ({err!r}); falling back to the CPU "
            "engine for the rest of this process",
            RuntimeWarning,
            stacklevel=2,
        )
        from merklekv_tpu.utils.tracing import get_metrics

        get_metrics().inc("device.fallbacks")


def device_failed() -> bool:
    """True once any device path has failed; callers use the CPU engine."""
    return _device_fallback
