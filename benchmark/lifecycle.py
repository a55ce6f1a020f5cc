"""Start-up as the program timed it, for the ``lifecycle.*`` per-layer readers.

The spans ``run.py`` hands a reader (``run["spans"]``) are those that started
inside the measured window; start-up ends before it opens. So the program
offers its start-up tree process-wide, as it offers what it compiled:
``swarm.telemetry.lifecycle_spans()`` (the ``lifecycle``-trace spans of the
process's live tracers: the benchmark's process has one volunteer) and
``utils.jaxenv.compile_log().summary(until=t)``. Both are cut at the window's
opening (``run["window"]["wall0"]``): the benchmark's reference check compiles
in the same process, after the window.

A program without them (a parent commit) gives nothing, and no error.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def span_seconds(run: Dict[str, Any], name: str) -> Optional[float]:
    """Seconds of the ``lifecycle``-trace span ``name`` that began last before
    the window opened; None where the program recorded none."""
    try:
        from distributedvolunteercomputing_tpu.swarm.telemetry import lifecycle_spans
    except ImportError:
        return None
    cut = run["window"]["wall0"]
    found = [s for s in lifecycle_spans()
             if s["name"] == name and s.get("dur_s") is not None and s["t0"] <= cut]
    return float(found[-1]["dur_s"]) if found else None


def compiled_before_window(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``CompileLog.summary`` of what ended before the window opened; None
    where the program's log cannot cut at a moment (it counts the backend alone)."""
    from distributedvolunteercomputing_tpu.utils.jaxenv import compile_log

    try:
        return compile_log().summary(until=run["window"]["wall0"])
    except TypeError:  # the log before PR 37: no `until`, the backend's seconds alone
        return None
