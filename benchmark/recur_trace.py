"""A looped model's loop over its passes in a run, for the ``recur.*`` readers.

The program declares a span ``recur.exit`` of its step's exit statistics
(``models/ouro.py:spans``: ``exit_entropy``, ``expected_passes``, the first and
the last pass's mean exit probability, with ``passes`` and ``layers`` as
attributes) and runs the passes under ``jax.named_scope("recur")``, a word of
its scope vocabulary (``utils/step_scopes.VOCABULARY``): an instruction whose
INNERMOST word is ``recur`` lies in the loop and in no block (``attention``,
``mlp``) and not in the head (``loss_head``, which runs after the loop).

A program with no such span or word (every other model, the parent of PR 64)
gives nothing."""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

from benchmark import moe_trace, scope_trace, trace

SPAN = "recur.exit"
SCOPE = "recur"


def exit_span_attribute(run: Dict[str, Any], key: str) -> List[float]:
    """``key`` of every ``recur.exit`` span of the window that carries it."""
    return [float((s.get("attrs") or {})[key]) for s in run["spans"]
            if s["name"] == SPAN and key in (s.get("attrs") or {})]


def exit_span_median(run: Dict[str, Any], key: str) -> Optional[float]:
    """The median of ``key`` over the window's ``recur.exit`` spans, or None where none carries it."""
    values = exit_span_attribute(run, key)
    return statistics.median(values) if values else None


def outside_blocks_ms(run: Dict[str, Any]) -> Optional[float]:
    """Milliseconds a step of the whole steps' own op time whose instruction
    the program's scope map puts under ``recur`` itself; None without a trace,
    a map, a whole step, or the word in the map's vocabulary."""
    if run.get("trace") is None:
        return None
    try:
        doc = scope_trace.scopes_of(run)
    except Exception:  # noqa: BLE001 - a map that cannot be built leaves the metric out, as the scope.* readers do
        return None
    found = moe_trace.events_in_whole_steps(run)
    if doc is None or found is None or SCOPE not in doc["vocabulary"]:
        return None
    n_steps, inside = found
    ns = 0.0
    for e, own_ns, _ in trace.self_times(inside):
        rec, _ = scope_trace.resolve(doc, e.name)
        if rec is not None and rec["scope"] == SCOPE:
            ns += own_ns
    return ns / n_steps / 1e6
