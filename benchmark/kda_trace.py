"""The delta-rule scan's loops in a device trace, for the ``kda.*`` readers.
``ops/kda.core`` is a ``lax.scan`` over the chunks, forward and (reversed)
backward, which XLA compiles to a ``while`` each. An ``XLA Ops`` event is named
by its instruction's text and a ``while`` spans its body's operations; a loop
has no name of its own, so the scan's loops are told from a step's other loops
by what they carry: the heads' states, one ``f32[batch, heads, value head, key
head]`` (the chunk to chunk state forward, its cotangent backward; read off a
v5e trace, my chip run, PR 52, call 9: all twelve loops of four mixers, forward,
recomputed forward and backward, and no other of the step's 36). A backward
loop is told from a forward one by how many arrays it holds a chunk at a time
(``[chunks, batch, ...]``): the six streams it reads, the states and the five
cotangents it stacks are twelve, where a forward loop holds six or seven.

A program without such a loop (every model without a KDA mixer, the parent of
PR 52) gives nothing."""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import family_flops, moe_trace, references, trace

_ARRAY_RE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
# a loop that holds more arrays by chunk than this is a backward one
FORWARD_HOLDS_AT_MOST = 8


def carried(text: str) -> List[Tuple[int, ...]]:
    """The shapes in a ``while``'s result (``%while.N = (s32[], f32[2,32,128,128]{...}, ...) while(...)``), [] for any other instruction."""
    if not trace.op_name(text).startswith("while"):
        return []
    result = text.split(" while(", 1)[0]
    return [tuple(int(n) for n in dims.split(",") if n) for dims in _ARRAY_RE.findall(result)]


def loop_events(run: Dict[str, Any]) -> Optional[Tuple[int, List[Tuple[bool, float]]]]:
    """(whole executions of the step program on chip 0, [(is backward, ns)] of
    the scan's loops inside them), or None where there is no trace, no such
    execution, no such loop or a configuration whose family has no KDA mixer."""
    cfg = run.get("config") or {}
    shapes_of = getattr(family_flops.load(cfg), "kda_scan_shapes", None)
    found = moe_trace.events_in_whole_steps(run)
    if found is None or shapes_of is None:
        return None
    seq_len = references.load(cfg["family"]).sizes(cfg)["seq_len"]
    state, by_chunk = shapes_of(cfg, run["tokens_per_step"] // seq_len, seq_len)
    n_steps, ops = found
    hits = []
    for e in ops:
        shapes = carried(e.name)
        if state in shapes:
            hits.append((sum(s[:2] == by_chunk for s in shapes) > FORWARD_HOLDS_AT_MOST, e.dur_ns))
    return (n_steps, hits) if hits else None
