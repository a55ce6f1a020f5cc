"""What the readers of the program's own timeline of the chip's queue share
(PR 72: ``loop.late_ms``, ``loop.held_ms``, ``loop.wait_unnamed_ms``,
``loop.wait_share``, ``loop.step_wall_max_over_median``,
``loop.wait_over_trace_idle``).

The program stamps every step twice, enqueued and done, and writes what
follows (``swarm/telemetry.ChipTimeline``): a ``loop.steps`` span every ten
steps with the stretch's ``late_s`` (the chip had finished and nothing was
enqueued), ``held_s`` (a step took longer than the running median of its own
time: it sat in the chip's queue behind something, or ran slow),
``step_s_p50`` and ``step_s_max``; and a ``loop.chip_wait`` span for every
wait over the program's thresholds. What a held step sat behind is resolved
when somebody reads, by the program's own ``telemetry.chip_waits``, which an
operator's report shares. A program without the spans or the function (the
parent of PR 72) gives every reader nothing.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark import trace as tr


def stretches(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The attributes of the window's ``loop.steps`` spans, each with its
    ``dur_s`` and, as ``hook_s``, the seconds of ``hook_waits`` that began in it."""
    hook = hook_waits(run)
    return [
        {**s["attrs"], "dur_s": float(s["dur_s"]),
         "hook_s": sum(w for t0, w in hook if s["t0"] <= t0 < s["t0"] + s["dur_s"])}
        for s in run["spans"]
        if s["name"] == "loop.steps" and s.get("dur_s") is not None and "late_s" in (s.get("attrs") or {})
    ]


def hook_waits(run: Dict[str, Any]) -> List[tuple]:
    """``(t0, seconds)`` of the window's late waits that fell into the loop's
    hook (``during`` = ``on_step``). In the benchmark the hook is the probe:
    its syncs at the window's ends and ten steps in, and in a traced run the
    profiler's start and stop, which stop the chip for seconds. They are the
    harness's and not the program's, so the readers of a whole window take
    them off; ``loop.wait_over_trace_idle`` keeps them (the device trace
    shows that idle time too)."""
    return [
        (s["t0"], float(s["dur_s"])) for s in run["spans"]
        if s["name"] == "loop.chip_wait" and s.get("dur_s") is not None
        and (s.get("attrs") or {}).get("kind") == "late" and s["attrs"].get("during") == "on_step"
    ]


def ms_a_period(run: Dict[str, Any], seconds: Optional[float]) -> Optional[float]:
    """``seconds`` of the window as milliseconds a launch-to-launch period, as
    ``loop.snapshot_ms`` divides; nothing without a whole period."""
    periods = run["stats"].get("rounds.in_window")
    if not periods or seconds is None:
        return None
    return seconds / periods * 1e3


def waits(run: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The window's ``loop.chip_wait`` spans, resolved by the program's
    ``chip_waits``, each with the span's ``t0``; None where the program has no
    such function or the window no ``loop.steps`` span (no timeline ran)."""
    if not stretches(run):
        return None
    try:
        from distributedvolunteercomputing_tpu.swarm import telemetry
    except ImportError:
        return None
    resolve = getattr(telemetry, "chip_waits", None)
    if resolve is None:
        return None
    return [{**w, "t0": s["t0"]} for s, w in zip(run["spans"], resolve(run["spans"])) if w is not None]


def traced_interval_on_the_spans_clock(run: Dict[str, Any]) -> Optional[tuple]:
    """The traced window as ``(t0, t1)`` on the clock of the spans' ``t0``,
    through a pair the run already holds: the start of the trace's
    ``bench:averager_call`` mark and the ``wall0`` the probe noted as that
    round's call began. None where it cannot align."""
    trace = run.get("trace")
    if trace is None:
        return None
    window, calls = trace.window(), trace.marks("bench:averager_call")
    began = run.get("window", {}).get("wall0")
    rounds = sorted((r for r in run.get("rounds", ()) if began is None or r["wall0"] >= began),
                    key=lambda r: r["wall0"])
    if window is None or not calls or not rounds:
        return None
    at = lambda ns: rounds[0]["wall0"] + (ns - calls[0].start_ns) / 1e9  # noqa: E731
    return at(window[0]), at(window[1])


def trace_idle_s(run: Dict[str, Any]) -> Optional[float]:
    """Chip 0's idle seconds in the traced window: ``loop.round_block_ms``'s quantity."""
    bi = tr.busy_idle(run["trace"]) if run.get("trace") is not None else None
    return None if bi is None else bi["window_s"] - bi["busy_s_per_chip"][0]
