"""Where JAX runs and where it keeps compiled programs.

Three decisions live here so every entry point makes them the same way:
the CPU test platform with N virtual devices (``pin_platform``), the
persistent compilation cache's directory (``enable_compile_cache``), and
the one is-this-a-TPU predicate (``tpu_backend``) — plus the two records
every result carries so it names what it ran on: the device
(``device_record``) and what was compiled (``compile_log``).
"""

from __future__ import annotations

import os
import re
import threading
from typing import Dict, List, Optional

# The in-checkout cache directory (git-ignored). The path is part of the
# cache key, so it is one fixed place: never ~, a temp name, a pid or a time.
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def force_host_devices(n: int) -> None:
    """Give the CPU platform at least ``n`` virtual devices. Effective only
    before the first backend init; no effect on other platforms."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    want = f"--xla_force_host_platform_device_count={n}"
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {want}"
    elif int(m.group(1)) < n:
        # A smaller existing count wouldn't give the promised minimum.
        os.environ["XLA_FLAGS"] = flags[: m.start()] + want + flags[m.end() :]


def pin_platform(platform: str, min_host_devices: int) -> None:
    """Tests and dry-runs: pin jax to ``platform`` (the CPU) with at least
    ``min_host_devices`` virtual host devices, before the first backend
    init."""
    force_host_devices(min_host_devices)
    import jax

    jax.config.update("jax_platforms", platform)


def enable_compile_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache; returns its directory,
    or None off-TPU.

    Volunteer churn is the framework's normal operating mode (SURVEY.md §1
    L3): every rejoin re-traces and re-compiles the train step before the
    volunteer contributes again. The persistent cache turns every rejoin
    after the first into a disk hit.

    The directory is placed from OUTSIDE: where ``JAX_COMPILATION_CACHE_DIR``
    is set jax reads it itself and no directory is set in code; otherwise it
    is ``COMPILE_CACHE_DIR``, one fixed path inside the checkout.

    TPU-only: XLA:CPU persists AOT results whose machine-feature stamp can
    fail at load (observed in-repo: `cpu_aot_loader` feature-mismatch spam +
    SIGILL warnings that broke a swarm e2e when the cache was enabled
    unconditionally), and CPU compiles are fast enough not to need a cache."""
    if not tpu_backend():
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program: the default 1s floor would skip the small
    # steps proxies/tests compile most often, and disk here is cheap.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def tpu_backend() -> bool:
    """True when the default backend is TPU silicon. The single source of
    truth for is-this-a-TPU decisions (bf16 compute dtype, pallas kernel
    routing, interpret vs compiled kernels)."""
    import jax

    return jax.default_backend() == "tpu"


def device_record() -> dict:
    """The device as jax reports it — what every result names itself by."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


class CompileLog:
    """What jax compiled in this process, heard from ``jax.monitoring``: per
    program name the count and the seconds spent in the backend compiler (or
    fetching the executable from the persistent cache), and the persistent
    cache's hits and misses."""

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self._programs: Dict[str, List[float]] = {}  # name -> [count, seconds]
        self._cache_hits = 0
        self._cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                rec = self._programs.setdefault(kw.get("fun_name", "?"), [0, 0.0])
                rec[0] += 1
                rec[1] += duration

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self._cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self._cache_misses += 1

    def summary(self, program: str) -> dict:
        """Totals, and ``program``'s own count and seconds (``jit(step)``
        compiled once means no recompilation after warm-up)."""
        with self._lock:
            count, seconds = self._programs.get(program, (0, 0.0))
            return {
                "programs": int(sum(c for c, _ in self._programs.values())),
                "seconds": round(sum(t for _, t in self._programs.values()), 3),
                "cache_hits": self._cache_hits,
                "cache_misses": self._cache_misses,
                "program": program,
                "program_compiles": int(count),
                "program_seconds": round(seconds, 3),
            }


_compile_log: Optional[CompileLog] = None
_compile_log_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process's one CompileLog (jax.monitoring listeners are
    process-wide and cannot be scoped to an object's lifetime); listening
    starts at the first call."""
    global _compile_log
    with _compile_log_lock:
        if _compile_log is None:
            _compile_log = CompileLog()
        return _compile_log
