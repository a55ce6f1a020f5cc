"""The expert layer's operations in a device trace, for the ``moe.*`` readers.

An ``XLA Ops`` event is named by its instruction's text: its name, the type
of its result, its operands. What the chip prints for the grouped matmuls (my
chip runs, PR 28, TPU v5e):

- megablox (a Pallas call with no ``name=`` of its own) is named after the
  jitted library function and the transforms around it: ``%gmm.16``,
  ``%tgmm.2`` (the backward by the weights' side) inside the step,
  ``%jvp_jit_gmm__.2``, ``%transpose_jvp_jit_tgmm___.2`` where it is
  differentiated alone;
- ``jax.lax.ragged_dot`` is the custom call ``%ragged-dot-none.N`` (its
  ``%ragged-dot-metadata.N`` is bookkeeping of microseconds and is counted as
  routing, not as a matmul).

The rest of the layer has no name of its own: the row gathers of the dispatch
(4.4 ms each, six a step), silu x up and the sums of cotangents are
``%fusion.37``, ``%multiply_multiply_fusion``, ``%add_any.4``. A ``jax.jit``
with a name around the row movers leaves it only in the instruction's
``metadata={op_name=...}``, which an event does not carry (compiled for a
described v5e: the gather is still ``%fusion.N``). What tells them from the
rest of the step is their result: the S x k routed rows lead it
(``bf16[131072,2048]``, ``s32[131072]``), and no other tensor of the step has
that many rows (tokens are S, the head's chunks ``[4,512,50304]``).

A program with no grouped matmul (a dense model, or the parent of PR 28) gives
nothing.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import trace

# the grouped matmuls themselves
GMM_RE = re.compile(r"(^|_)t?gmm_*(\.\d+)?$|^ragged-dot(?!-metadata)")
# the rest of the expert layer that the chip names readably: the sort of the
# assignments, the router's top-k, and the grouped matmuls' bookkeeping
ROUTING_RE = re.compile(r"^(sort|top-?k|topk|ragged-dot-metadata)", re.IGNORECASE)
_RESULT_RE = re.compile(r"^%?[^\s=]+\s*=\s*\(?[a-z0-9]+\[(\d+)[,\]]")


def routed_rows(run: Dict[str, Any]) -> Optional[int]:
    """S x k: the assignments a layer routes in one step, or None for a
    configuration without experts."""
    k = (run.get("config") or {}).get("num_experts_per_tok")
    return int(run["tokens_per_step"]) * int(k) if k else None


def leads_with(text: str, rows: Optional[int]) -> bool:
    """Whether the (first) result of the instruction ``text`` has ``rows`` as
    its leading dimension: a tensor over the routed rows."""
    m = _RESULT_RE.match(text)
    return bool(m) and rows is not None and int(m.group(1)) == rows


def layer_ns(ops: List[trace.Event], rows: Optional[int]) -> float:
    """Nanoseconds in which some operation of the expert layer ran: the
    grouped matmuls, the named routing operations and whatever produces a
    tensor over the routed rows. A union of intervals, so that an operation
    that spans others (a sort's loop) is not counted twice."""
    return trace.length(trace.merge(
        (e.start_ns, e.end_ns) for e in ops
        if GMM_RE.search(trace.op_name(e.name)) or ROUTING_RE.search(trace.op_name(e.name))
        or leads_with(e.name, rows)))


def events_in_whole_steps(run: Dict[str, Any]) -> Optional[Tuple[int, List[trace.Event]]]:
    """(number of whole executions of the step program on chip 0, every
    operation's event inside them), or None where the run has no trace or no
    such execution."""
    if run.get("trace") is None:
        return None
    steps = trace.program_runs(run["trace"], run["step_program"])
    planes = run["trace"].device_planes()
    if not steps or not planes:
        return None
    line = planes[0].line(trace.OPS_LINE)
    events = sorted(line.events if line is not None else [], key=lambda e: e.start_ns)
    starts = [e.start_ns for e in events]
    inside: List[trace.Event] = []
    for step in steps:
        lo = bisect.bisect_left(starts, step.start_ns)
        hi = bisect.bisect_right(starts, step.end_ns)
        inside += [e for e in events[lo:hi] if e.end_ns <= step.end_ns]
    return len(steps), inside
