"""The step's device time by the program's own scopes, for the ``scope.*`` readers.

The program offers its step's scope map (``utils.step_scopes.step_scopes``:
instruction name -> scope word, pass, result type, whether a fusion swallowed
more than one group; the vocabulary and its groups come with it). An ``XLA
Ops`` event is named by its instruction's whole text, so ``trace.op_name`` of
it is the key into that map. Over the whole executions of the step's program
on chip 0, each event's OWN time (``trace.self_times``: a ``while`` without
its body) goes to its instruction's group and pass; an event whose name the
map does not hold, or whose result type differs from the map's (the map of
another executable), is unresolved and goes nowhere. The own times sum to the
steps' busy time, so the groups and the unresolved rest do too.

The program builds the map when it is asked (from jax's own caches in
milliseconds; a compile if they have dropped the step): it is asked here,
after the window and the reference check, once a run. What it took, the table
by group and pass and the share of time in ``mixed`` fusions go to stderr, and
the map beside the trace (``.bench_work/<cell>/step_scopes.json``) for
``experiments/step_ops_in_trace.py``.

A program without the accessor (a parent commit) gives nothing, and no error.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys
import time
from typing import Any, Dict, Optional, Tuple

from benchmark import manifest, moe_trace, trace

_ARRAY_RE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")
_RESULT_RE = re.compile(r"^%?[^\s=]+\s*=\s*(.*)$", re.DOTALL)


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


@functools.lru_cache(maxsize=None)  # an instruction's text comes back every step and every turn of a loop
def shapes(result: str) -> Tuple[str, ...]:
    """A result type as printed -> its arrays without their layouts
    (``(s32[]{:T(128)}, f32[2,8]{1,0})`` -> ``("s32[]", "f32[2,8]")``): a
    trace and a module's text may print a layout differently."""
    return tuple(_ARRAY_RE.findall(result))


@functools.lru_cache(maxsize=None)
def event_result(text: str) -> Tuple[str, ...]:
    """The arrays of the result in an event's name (an instruction's text);
    a tuple's type runs to its closing parenthesis."""
    m = _RESULT_RE.match(text)
    if not m:
        return ()
    rest = m.group(1)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return shapes(rest[: i + 1])
        return shapes(rest)
    return shapes(rest.split(" ", 1)[0])


def scopes_of(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The program's scope document for the step program of ``run``'s trace,
    or None where the program has no accessor or remembered no such step."""
    try:
        from distributedvolunteercomputing_tpu.utils import step_scopes as program
    except ImportError:
        return None
    for name in program.remembered():
        doc = program.step_scopes(name)
        if doc is not None and re.search(run["step_program"], doc["module"]):
            return doc
    return None


def resolve(doc: Dict[str, Any], event_name: str) -> Tuple[Optional[Dict[str, Any]], str]:
    """(the map's record of the instruction an event is named by, "") where the
    map holds it with the event's result type; else (None, why not)."""
    rec = doc["map"].get(trace.op_name(event_name))
    if rec is None:
        return None, "no such instruction"
    if shapes(rec["result"]) != event_result(event_name):
        return None, "another result"
    return rec, ""


def attribute(run: Dict[str, Any], doc: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Nanoseconds of the whole steps' own op time by group, by pass and by
    (group, pass), unresolved, and in ``mixed`` fusions; None without a trace
    or a whole execution of the step."""
    found = moe_trace.events_in_whole_steps(run)
    if found is None:
        return None
    n_steps, inside = found
    table: Dict[str, Dict[str, float]] = {}
    out = {"steps": n_steps, "total_ns": 0.0, "unresolved_ns": 0.0, "mixed_ns": 0.0,
           "unresolved": {}}
    for e, own_ns, _ in trace.self_times(inside):
        out["total_ns"] += own_ns
        rec, why = resolve(doc, e.name)
        if rec is None:
            out["unresolved_ns"] += own_ns
            out["unresolved"].setdefault(trace.op_name(e.name), [why, 0.0])[1] += own_ns
            continue
        by_pass = table.setdefault(doc["vocabulary"].get(rec["scope"] or "", "other"), {})
        by_pass[rec["pass"]] = by_pass.get(rec["pass"], 0.0) + own_ns
        if rec["mixed"]:
            out["mixed_ns"] += own_ns
    out["table"] = table
    return out


_found: list = []  # [the trace last read, attribute()'s result for it]: made once a run for the nine readers


def by_scope(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``attribute`` of the run's trace; None where there is no trace, no
    accessor, no such program or no whole step."""
    if run.get("trace") is None:
        return None
    if not _found or _found[0] is not run["trace"]:
        _found[:] = [run["trace"], _read(run)]
    return _found[1]


def _read(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    t = time.perf_counter()
    try:
        doc = scopes_of(run)
    except Exception as e:  # noqa: BLE001 - a map that cannot be built leaves the metrics out, not the line
        log(f"scopes: no map ({type(e).__name__}: {e})")
        return None
    if doc is None:
        return None
    asked_s = time.perf_counter() - t
    got = attribute(run, doc)
    if got is None:
        return None
    work = os.path.join(manifest.REPO_ROOT, ".bench_work", run["cell"]["name"])
    if os.path.isdir(work):
        with open(os.path.join(work, "step_scopes.json"), "w") as fh:
            json.dump(doc, fh)
    steps = got["steps"]
    total = got["total_ns"] or 1.0
    worst = sorted(got["unresolved"].items(), key=lambda kv: -kv[1][1])[:8]
    log("scopes: " + json.dumps({
        "program": doc["program"], "instructions": len(doc["map"]), "map_seconds": doc["seconds"],
        "asked_s": round(asked_s, 3), "steps": steps, "op_ms_a_step": round(total / steps / 1e6, 3),
        "ms_a_step": {g: {p: round(ns / steps / 1e6, 3) for p, ns in sorted(by_pass.items())}
                      for g, by_pass in sorted(got["table"].items())},
        "mixed_share": round(got["mixed_ns"] / total, 5),
        "unresolved_share": round(got["unresolved_ns"] / total, 5),
        "unresolved_most": [[name, why, round(ns / steps / 1e6, 4)] for name, (why, ns) in worst],
    }))
    return got


def group_ms(run: Dict[str, Any], group: str) -> Optional[float]:
    """Milliseconds a step in ``group``, all passes; 0 for a group the step
    has nothing under."""
    got = by_scope(run)
    if got is None:
        return None
    return sum(got["table"].get(group, {}).values()) / got["steps"] / 1e6


def share(run: Dict[str, Any], what: str) -> Optional[float]:
    """Percent of the steps' own op time that is ``refwd`` (what the
    checkpoints recompute) or ``unresolved``."""
    got = by_scope(run)
    if got is None or not got["total_ns"]:
        return None
    if what == "unresolved":
        ns = got["unresolved_ns"]
    else:
        ns = sum(by_pass.get(what, 0.0) for by_pass in got["table"].values())
    return 100.0 * ns / got["total_ns"]
