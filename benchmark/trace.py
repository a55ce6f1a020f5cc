"""From a profiler trace (``.xplane.pb``) to busy and idle time, per-program
durations, collective time and labelled idle gaps.

``jax.profiler.ProfileData`` reads the file; everything after that works on a
plain structure (planes -> lines -> events, times in nanoseconds from the
start of the trace) that a test can build by hand or load from JSON.

What a TPU trace looks like (read off a v5e trace, PERF.md section 5): one
plane per chip named ``/device:TPU:<n>``; on it the line ``XLA Modules`` holds
one event per execution of a compiled program (``jit_step(<fingerprint>)``),
the line ``XLA Ops`` one event per HLO operation on the chip's one instruction
stream, named by the instruction's whole text (``%fusion.450 = bf16[16,16,
1024,1024]{...} fusion(...)``) and nested where an operation contains others
(a ``while`` spans its body's operations), and the line ``Async XLA Ops`` the
asynchronous copies and collectives that run beside that stream. Host threads
are lines of ``/host:CPU``; a ``jax.profiler.TraceAnnotation`` shows there
under its own name, on the same clock as the device planes.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_OPS_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)"
)

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]

    def line(self, name: str) -> Optional[Line]:
        for ln in self.lines:
            if ln.name == name:
                return ln
        return None


@dataclasses.dataclass
class Trace:
    planes: List[Plane]

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        planes = []
        for plane in data.planes:
            if not (DEVICE_PLANE_RE.match(plane.name) or plane.name == HOST_PLANE):
                continue
            lines = []
            for line in plane.lines:
                if plane.name == HOST_PLANE:
                    # Host threads carry thousands of runtime events; only
                    # the benchmark's own annotations are read.
                    events = [
                        Event(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events if e.name.startswith("bench:")
                    ]
                    if not events:
                        continue
                else:
                    events = [
                        Event(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
                lines.append(Line(line.name, events))
            planes.append(Plane(plane.name, lines))
        return cls(planes)

    @classmethod
    def from_json(cls, doc: Dict[str, Any]) -> "Trace":
        return cls([
            Plane(p["name"], [
                Line(ln["name"], [Event(n, float(s), float(d)) for n, s, d in ln["events"]])
                for ln in p["lines"]
            ])
            for p in doc["planes"]
        ])

    # -- what is in it ------------------------------------------------------

    def device_planes(self) -> List[Plane]:
        planes = [p for p in self.planes if DEVICE_PLANE_RE.match(p.name)]
        return sorted(planes, key=lambda p: int(DEVICE_PLANE_RE.match(p.name).group(1)))

    def marks(self, name: str) -> List[Event]:
        """The benchmark's own host annotations called ``name``, by start."""
        out = [
            e for p in self.planes if p.name == HOST_PLANE
            for ln in p.lines for e in ln.events if e.name == name
        ]
        return sorted(out, key=lambda e: e.start_ns)

    def window(self) -> Optional[Interval]:
        """The traced window: from the end of ``bench:trace_begin`` to the
        start of ``bench:trace_end`` where the benchmark marked them, else the
        extent of the device events."""
        begin, end = self.marks("bench:trace_begin"), self.marks("bench:trace_end")
        if begin and end:
            return (begin[0].end_ns, end[-1].start_ns)
        evs = [e for p in self.device_planes() for ln in p.lines for e in ln.events]
        if not evs:
            return None
        return (min(e.start_ns for e in evs), max(e.end_ns for e in evs))


# -- interval arithmetic ------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of ``a`` (disjoint, sorted) that no interval of ``b``
    (disjoint, sorted) covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# -- reductions ---------------------------------------------------------------


def _ops(plane: Plane, line: str = OPS_LINE) -> List[Event]:
    ln = plane.line(line)
    return ln.events if ln is not None else []


_HLO_NAME_RE = re.compile(r"^%?([^\s=]+)(?:\s*=\s*\(?([a-z0-9]+\[[0-9,]*\]))?")


def op_name(text: str) -> str:
    """``%fusion.450 = bf16[16,16,1024,1024]{...} fusion(...)`` -> ``fusion.450``."""
    m = _HLO_NAME_RE.match(text)
    return m.group(1) if m else text


def op_label(text: str) -> str:
    """The operation's name with the type of its (first) result, which is what
    tells one fusion from another: ``fusion.450 bf16[16,16,1024,1024]``."""
    m = _HLO_NAME_RE.match(text)
    if not m:
        return text[:80]
    return m.group(1) if not m.group(2) else f"{m.group(1)} {m.group(2)}"


def is_collective(text: str) -> bool:
    return bool(COLLECTIVE_RE.match(op_name(text)))


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float, bool]]:
    """For the events of one line, where an operation that contains others
    spans them: (event, its own nanoseconds without its children's, whether
    it is a leaf)."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    own = [e.dur_ns for e in order]
    leaf = [True] * len(order)
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= order[stack[-1]].end_ns:
            own[stack[-1]] -= e.dur_ns
            leaf[stack[-1]] = False
        stack.append(i)
    return [(e, max(0.0, t), lf) for e, t, lf in zip(order, own, leaf)]


def busy_intervals(plane: Plane, window: Interval) -> List[Interval]:
    """When an operation ran on this chip, inside ``window``."""
    return merge(clip(((e.start_ns, e.end_ns) for e in _ops(plane)), *window))


def busy_idle(trace: Trace) -> Optional[Dict[str, Any]]:
    """Seconds busy (the union of the op intervals, averaged over the chips),
    the window's length and the idle share; None where no device op ran."""
    window = trace.window()
    planes = trace.device_planes()
    if window is None or not planes:
        return None
    per_chip = [length(busy_intervals(p, window)) / 1e9 for p in planes]
    if not any(per_chip):
        return None
    window_s = (window[1] - window[0]) / 1e9
    busy_s = sum(per_chip) / len(per_chip)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "busy_s_per_chip": per_chip,
        "chips": len(planes),
    }


def program_runs(trace: Trace, pattern: str, chip: int = 0) -> List[Event]:
    """Executions of the compiled programs whose name matches ``pattern``
    on one chip, inside the window, by start."""
    planes = trace.device_planes()
    window = trace.window()
    if not planes or window is None:
        return []
    ln = planes[min(chip, len(planes) - 1)].line(MODULES_LINE)
    if ln is None:
        return []
    rx = re.compile(pattern)
    return sorted(
        (e for e in ln.events
         if rx.search(e.name) and e.start_ns >= window[0] and e.end_ns <= window[1]),
        key=lambda e: e.start_ns,
    )


def program_totals(trace: Trace, chip: int = 0) -> Dict[str, Tuple[int, float]]:
    """Every program that ran on ``chip``: name (fingerprint stripped) ->
    (executions, seconds)."""
    out: Dict[str, Tuple[int, float]] = {}
    for e in program_runs(trace, "", chip):
        name = re.sub(r"\(\d+\)$", "", e.name)
        n, s = out.get(name, (0, 0.0))
        out[name] = (n + 1, s + e.dur_ns / 1e9)
    return out


def gaps_between(runs: Sequence[Event]) -> List[float]:
    """Idle nanoseconds between consecutive executions."""
    return [max(0.0, b.start_ns - a.end_ns) for a, b in zip(runs, runs[1:])]


def collective_time(trace: Trace) -> Optional[Dict[str, float]]:
    """Seconds of collective operations per chip (mean over chips), and the
    part of them during which no other operation ran on that chip."""
    window = trace.window()
    planes = trace.device_planes()
    if window is None or not planes:
        return None
    total, exposed = [], []
    for p in planes:
        # Synchronous collectives sit in the instruction stream, asynchronous
        # ones (start/done pairs) on the line beside it.
        coll = merge(clip(((e.start_ns, e.end_ns)
                           for e in [*_ops(p), *_ops(p, ASYNC_OPS_LINE)]
                           if is_collective(e.name)), *window))
        # Compute is the leaf operations: a `while` spans its whole body.
        compute = merge(clip(((e.start_ns, e.end_ns) for e, _, leaf in self_times(_ops(p))
                              if leaf and not is_collective(e.name)), *window))
        total.append(length(coll) / 1e9)
        exposed.append(length(subtract(coll, compute)) / 1e9)
    return {
        "collective_s": sum(total) / len(total),
        "exposed_s": sum(exposed) / len(exposed),
        "window_s": (window[1] - window[0]) / 1e9,
    }


def top_ops(trace: Trace, n: int = 10, chip: int = 0) -> List[Tuple[str, float]]:
    """The device operations that took most time on ``chip``: (label, seconds)
    of their own time (an operation's children are not counted twice),
    occurrences of one operation summed."""
    planes = trace.device_planes()
    window = trace.window()
    if not planes or window is None:
        return []
    totals: Dict[str, float] = {}
    for e, own_ns, _ in self_times(_ops(planes[min(chip, len(planes) - 1)])):
        if e.start_ns >= window[0] and e.end_ns <= window[1]:
            label = op_label(e.name)
            totals[label] = totals.get(label, 0.0) + own_ns / 1e9
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: Trace, labels: Sequence[Tuple[str, Interval]], n: int = 10,
              chip: int = 0) -> List[Tuple[str, float]]:
    """The longest idle gaps on ``chip``: (label, seconds). A gap takes the
    label of the first entry of ``labels`` (name, interval on the trace's
    clock; earlier entries take precedence) that covers at least half of it,
    else ``unattributed``."""
    planes = trace.device_planes()
    window = trace.window()
    if not planes or window is None:
        return []
    busy = busy_intervals(planes[min(chip, len(planes) - 1)], window)
    gaps = sorted(subtract([window], busy), key=lambda g: g[0] - g[1])[:n]
    out = []
    for g in gaps:
        name = "unattributed"
        for label, iv in labels:
            if overlap(g, iv) >= 0.5 * (g[1] - g[0]):
                name = label
                break
        out.append((name, (g[1] - g[0]) / 1e9))
    return out


def describe(trace: Trace, top: int = 12) -> str:
    """What a trace holds, for reading one by hand."""
    rows = []
    for p in trace.planes:
        rows.append(f"plane {p.name}")
        for ln in p.lines:
            rows.append(f"  line {ln.name!r}: {len(ln.events)} events")
            totals: Dict[str, List[float]] = {}
            for e in ln.events:
                rec = totals.setdefault(e.name, [0, 0.0])
                rec[0] += 1
                rec[1] += e.dur_ns
            for name, (cnt, ns) in sorted(totals.items(), key=lambda kv: -kv[1][1])[:top]:
                rows.append(f"    {ns / 1e6:10.3f} ms  x{cnt:<6d} {name[:100]}")
    return "\n".join(rows)
