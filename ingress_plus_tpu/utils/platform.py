"""Platform selection, device identity and the compile cache.

One process holds a chip: nothing here probes a device on behalf of
another process, and nothing falls back to CPU when the default backend
is missing — a chip that does not answer is an error at the first
backend touch.  CPU is an explicit choice (``JAX_PLATFORMS=cpu`` in the
environment, or :func:`force_cpu_devices` in-process for tests and
CPU-only tools that need several virtual devices).
"""

from __future__ import annotations

import os
import re
import threading
from pathlib import Path
from typing import Optional

_COUNT_FLAG = "--xla_force_host_platform_device_count"

#: the fixed fallback location of the persistent compile cache: the
#: path is part of what makes a later start hit it, so it never moves
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_cpu_devices(n_devices: int = 8) -> None:
    """Force the CPU platform with ``n_devices`` virtual devices.

    Must be called before any jax backend touch (jax.devices, device_put,
    jit dispatch...).  Rewrites an existing device-count flag rather than
    keeping a stale value, so a wrapper-exported XLA_FLAGS with a
    different count can't silently win.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    want = "%s=%d" % (_COUNT_FLAG, n_devices)
    if _COUNT_FLAG in flags:
        flags = re.sub(re.escape(_COUNT_FLAG) + r"=\d+", want, flags)
    else:
        flags = (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")


def assert_cpu_devices(n_devices: int) -> None:
    """Fail loudly (instead of mysteriously later) if the virtual mesh
    didn't materialize — e.g. a backend was already initialized with
    different flags before force_cpu_devices ran."""
    import jax

    devs = jax.devices()
    if len(devs) != n_devices or devs[0].platform != "cpu":
        raise RuntimeError(
            "expected %d virtual CPU devices, got %d x %s. A backend was"
            " initialized before force_cpu_devices(); rerun in a fresh"
            " process." % (n_devices, len(devs),
                           devs[0].platform if devs else "none"))


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache somewhere a later start
    finds it again; call before the first backend touch.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    is set in code.  Unset: ``<checkout>/.jax_cache`` — except on an
    explicit CPU run (``JAX_PLATFORMS=cpu`` / ``--platform cpu``),
    which gets none: XLA:CPU compiles these programs in milliseconds,
    and its cache loader logs kilobytes of machine-feature text to
    stderr on every hit.  Unless the environment says otherwise every
    executable is cached: the served path is hundreds of small
    programs, most of them under JAX's default one-second floor.
    Returns the directory in use, or None."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        if jax.config.jax_platforms == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return env_dir or str(REPO_CACHE_DIR)


def device_memory_peak_bytes() -> Optional[int]:
    """``memory_stats()["peak_bytes_in_use"]``, the highest over the
    local devices, read now (``ipt_device_memory_peak_bytes``); None
    where the backend reports none (CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    known = [p for p in peaks if p is not None]
    return max(known) if known else None


def on_tpu() -> bool:
    """Whether the Mosaic kernels compile on the default backend — the
    one place this is decided."""
    import jax

    return jax.default_backend() == "tpu"


def device_block() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them; every printed measurement and parity result carries this."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


class _CompileCounter:
    """Counts JAX's backend-compile events.  JAX's monitoring registry
    is process-wide and has no unregister, so one instance lives for
    the process; the pool-threaded warm-up compiles concurrently, hence
    the lock."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.n = 0
        self.listening = False
        self._lock = threading.Lock()

    def __call__(self, event: str, duration_secs: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.n += 1


_compile_counter = _CompileCounter()


def backend_compiles() -> int:
    """Every XLA backend compile in this process since the first call —
    jitted functions AND the eager per-shape programs JAX builds for ops
    outside jit, which a shape-keyed gauge cannot see.  Between two
    readings with no background compile (no hot swap, no rollout) the
    difference is what the traffic in between waited on.  The first
    call installs the listener and returns 0."""
    if not _compile_counter.listening:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_compile_counter)
        _compile_counter.listening = True
    return _compile_counter.n
