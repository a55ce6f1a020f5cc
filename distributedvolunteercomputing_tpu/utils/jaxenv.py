"""Where JAX runs and where it keeps compiled programs.

Three decisions live here so every entry point makes them the same way:
the CPU test platform with N virtual devices (``pin_platform``), the
persistent compilation cache's directory (``enable_compile_cache``), and
the one is-this-a-TPU predicate (``tpu_backend``) — plus the two records
every result carries so it names what it ran on: the device
(``device_record``) and what was compiled (``compile_log``).
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
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
    program name (``jit(step)``) the count and the seconds of each stage a
    first call goes through, and the persistent cache's hits and misses.

    The stages: ``trace`` (the Python function to a jaxpr; nested ``jit``
    calls are part of the program that called them and are not counted
    again), ``lower`` (jaxpr to an MLIR module), ``backend`` (the backend
    compiler, or fetching the executable from the persistent cache) and
    ``cache_load`` (the fetch alone: the part of ``backend`` that a cache hit
    costs). Each event is kept with the wall time at which it ended and the
    thread it ended on, so that ``summary`` can count what happened before or
    after a moment, or on one thread."""

    # Events kept one by one; older ones are folded into totals that every
    # unfiltered summary (and every ``until``) still counts.
    MAX_EVENTS = 4096
    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _STAGES = {
        _TRACE: "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        _BACKEND: "backend",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
        "/jax/compilation_cache/compile_time_saved_sec": "cache_saved",
    }
    _COUNTS = {
        "/jax/compilation_cache/cache_hits": "hit",
        "/jax/compilation_cache/cache_misses": "miss",
    }

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        # (wall time, thread, stage, program, seconds), oldest first
        self._events: "collections.deque[tuple]" = collections.deque()
        self._folded: Dict[tuple, List[float]] = {}  # (stage, program) -> [count, seconds]
        # Per thread: how deep in nested traces it is, and the program whose
        # backend compile it is in (the cache's events carry no name).
        self._here = threading.local()
        jax.monitoring.register_scalar_listener(self._on_begin)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_begin(self, event: str, value: float, **kw) -> None:
        # jax records a scalar (the start time) as a timed stage begins.
        if event == self._TRACE:
            self._here.depth = getattr(self._here, "depth", 0) + 1
        elif event == self._BACKEND:
            self._here.program = kw.get("fun_name", "?")

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        stage = self._STAGES.get(event)
        if stage is None:
            return
        if stage == "trace":
            self._here.depth = max(getattr(self._here, "depth", 1) - 1, 0)
            if self._here.depth:
                return  # a jit called while another is traced: inside that one's seconds
            name = f"jit({kw.get('fun_name', '?')})"  # as the later stages name it
        elif stage in ("cache_load", "cache_saved"):
            name = getattr(self._here, "program", "?")
        else:
            name = kw.get("fun_name", "?")
        self._keep(stage, name, duration)
        if stage == "backend":
            self._here.program = "?"

    def _on_event(self, event: str, **kw) -> None:
        kind = self._COUNTS.get(event)
        if kind is not None:
            self._keep(kind, getattr(self._here, "program", "?"), 0.0)

    def _keep(self, stage: str, program: str, seconds: float) -> None:
        with self._lock:
            self._events.append((time.time(), threading.get_ident(), stage, program, seconds))
            if len(self._events) > self.MAX_EVENTS:
                _, _, old_stage, old_program, old_seconds = self._events.popleft()
                rec = self._folded.setdefault((old_stage, old_program), [0, 0.0])
                rec[0] += 1
                rec[1] += old_seconds

    def summary(
        self,
        program: str = "",
        until: Optional[float] = None,
        since: Optional[float] = None,
        thread: Optional[int] = None,
    ) -> dict:
        """Totals, and ``program``'s own count and seconds (``jit(step)``
        compiled once means no recompilation after warm-up). ``until`` /
        ``since`` (wall times) and ``thread`` (an ident) count only the
        events that ended by then, from then on, on that thread."""
        with self._lock:
            acc: Dict[tuple, List[float]] = (
                {k: list(v) for k, v in self._folded.items()}
                if since is None and thread is None else {}
            )
            for wall, ident, stage, name, seconds in self._events:
                if (
                    (until is None or wall <= until)
                    and (since is None or wall >= since)
                    and (thread is None or ident == thread)
                ):
                    rec = acc.setdefault((stage, name), [0, 0.0])
                    rec[0] += 1
                    rec[1] += seconds

        def count(stage: str) -> int:
            return int(sum(c for (s, _), (c, _) in acc.items() if s == stage))

        def seconds(stage: str) -> float:
            return round(sum((t for (s, _), (_, t) in acc.items() if s == stage), 0.0), 3)

        # A program's seconds over all stages (the cache's are inside ``backend``).
        whole: Dict[str, Dict[str, float]] = {}
        for (stage, name), (_, t) in acc.items():
            if stage in ("trace", "lower", "backend"):
                whole.setdefault(name, {"trace": 0.0, "lower": 0.0, "backend": 0.0})[stage] += t
        slowest = sorted(whole.items(), key=lambda kv: -sum(kv[1].values()))[:5]
        compiles, compile_seconds = acc.get(("backend", program), (0, 0.0))
        return {
            "programs": count("backend"),
            "seconds": seconds("backend"),
            "cache_hits": count("hit"),
            "cache_misses": count("miss"),
            "program": program,
            "program_compiles": int(compiles),
            "program_seconds": round(compile_seconds, 3),
            "trace_seconds": seconds("trace"),
            "lower_seconds": seconds("lower"),
            "cache_load_seconds": seconds("cache_load"),
            "cache_saved_seconds": seconds("cache_saved"),
            "slowest": [
                {"program": name, "seconds": round(sum(st.values()), 3),
                 **{f"{stage}_s": round(t, 3) for stage, t in st.items()}}
                for name, st in slowest
            ],
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
