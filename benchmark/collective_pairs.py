"""Collective time in a device trace whose asynchronous collectives are PAIRS OF
FUSIONS, for the ``device.collective_all_*`` readers.

``trace.collective_time`` (``device.collective_share`` / ``collective_exposed``)
knows a collective by its operation's name: ``all-reduce.N`` on the instruction
stream, ``all-reduce-start.N`` on ``Async XLA Ops``. The TPU compiler's
asynchronous collective is neither (read off a v5e trace of the ``dp=2,tp=2``
step compiled with ``xla_enable_async_all_reduce`` and
``xla_tpu_enable_async_collective_fusion_fuse_all_reduce``, my chip run, PR 57,
call 3): two ``kind=kCustom`` fusions on ``XLA Ops``,

    %async-collective-start.2 = (bf16[8,1024,1280]{...}, ...) fusion(...), calls=...
    %async-collective-done.2 = bf16[8,1024,1280]{...} fusion(...), calls=...

with the operations the scheduler found to run beside the transfer between
them, and nothing on ``Async XLA Ops``. The accepted readers therefore count
only what stayed synchronous. Here a pair is a collective from its start
fusion's beginning to its done fusion's end (an upper bound of the transfer:
the done may come long after the last byte), the two fusions themselves are
time on the instruction stream that no other operation shares (the wait nothing
hid), and the synchronous collectives count as they did.

The four-chip step had such pairs before PR 57 too (the compiler makes the
weights' all-gathers asynchronous unasked: my chip run, PR 57, call 5, the
parent's trace), so the readers speak on both sides of that PR. A program
without such a fusion (every one-chip step) gives nothing: the accepted readers
say all there is to say there."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import trace

PAIR_RE = re.compile(r"^async-collective-(start|done)((?:\.\d+)?)$")


def pair_part(text: str) -> Optional[Tuple[str, str]]:
    """("start" | "done", the pair's number as ``".2"`` or ``""``) of a start or
    done fusion's event, None for any other operation."""
    m = PAIR_RE.match(trace.op_name(text))
    return (m.group(1), m.group(2)) if m else None


def pairs(events: Sequence[trace.Event]) -> List[Tuple[trace.Event, trace.Event]]:
    """(start, done) of every pair among one line's events: a done closes the
    latest start of its number (a scanned layer runs the same pair once an
    iteration). A done whose start is not among the events is left out."""
    open_starts: Dict[str, trace.Event] = {}
    out = []
    for e in sorted(events, key=lambda e: e.start_ns):
        part = pair_part(e.name)
        if part is None:
            continue
        if part[0] == "start":
            open_starts[part[1]] = e
        elif (start := open_starts.pop(part[1], None)) is not None:
            out.append((start, e))
    return out


def collective_time(tr: trace.Trace) -> Optional[Dict[str, float]]:
    """As ``trace.collective_time``, with the pairs, mean over chips:
    ``collective_s``, seconds a chip has some collective under way (a named
    one, or a pair between its start's beginning and its done's end), and
    ``exposed_s``, the seconds of named collectives and of the pairs' own start
    and done fusions during which no other operation ran. The inside of a pair
    is not exposed time whatever runs there: the gaps between the operations
    beside a transfer are the instruction stream's, not a wait for the link.
    None where no chip ran a start or done fusion."""
    window = tr.window()
    planes = tr.device_planes()
    if window is None or not planes:
        return None
    total, exposed, n_parts = [], [], 0
    for p in planes:
        ops = trace._ops(p)
        parts = [e for e in ops if pair_part(e.name) is not None]
        n_parts += len(parts)
        spans = [(s.start_ns, d.end_ns) for s, d in pairs(parts)]
        # on the instruction stream or beside it with a collective's own name, and the pairs' two fusions
        stream = [(e.start_ns, e.end_ns)
                  for e in [*ops, *trace._ops(p, trace.ASYNC_OPS_LINE)]
                  if trace.is_collective(e.name) or pair_part(e.name) is not None]
        compute = trace.merge(trace.clip(
            ((e.start_ns, e.end_ns) for e, _, leaf in trace.self_times(ops)
             if leaf and not trace.is_collective(e.name) and pair_part(e.name) is None), *window))
        total.append(trace.length(trace.merge(trace.clip([*stream, *spans], *window))) / 1e9)
        exposed.append(trace.length(trace.subtract(trace.merge(trace.clip(stream, *window)), compute)) / 1e9)
    if not n_parts:
        return None
    return {
        "collective_s": sum(total) / len(total),
        "exposed_s": sum(exposed) / len(exposed),
        "window_s": (window[1] - window[0]) / 1e9,
    }


def share(run, key: str) -> Optional[float]:
    """``key`` (``collective_s`` or ``exposed_s``) of a traced run as a percentage
    of its window, None where there is no trace or no pair in it."""
    if run.get("trace") is None:
        return None
    c = collective_time(run["trace"])
    if c is None or c["window_s"] <= 0:
        return None
    return 100.0 * c[key] / c["window_s"]
