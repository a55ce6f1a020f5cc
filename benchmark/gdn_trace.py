"""The scalar-decay delta rule's loops in a device trace, for the ``gdn.*``
readers. ``ops/gdn.core`` is a ``lax.scan`` over the chunks, forward and
(reversed) backward, which XLA compiles to a ``while`` each. An ``XLA Ops``
event is named by its instruction's text and a ``while`` spans its body's
operations; a loop has no name of its own, so the scan's loops are told from a
step's other loops by what they carry: the value heads' states, one ``f32[batch,
value heads, value head, key head]`` (the chunk to chunk state forward, its
cotangent backward).

A backward loop is told from a forward one by something a scan that works IN
PLACE keeps whatever else the compiler does to it: the WHOLE streams it carries,
``[batch, T, ..]`` arrays that its steps read or write a chunk of. Forward they
are the three it reads (``qkv``, ``g``, ``beta``) and the one it writes (``o``):
four; backward the three, ``o``'s cotangent and the three cotangents it writes:
seven (``tests/test_tpu_compile.py`` pins both counts on the step compiled for a
described v5e). Not ``kda_trace.FORWARD_HOLDS_AT_MOST``: that one counts arrays
stacked by chunk, which ``ops/kda.py`` has not made since PR 55.

A program without such a loop (every model without a Gated DeltaNet, the parent
of PR 67) gives nothing."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark import family_flops, kda_trace, moe_trace, references

# a loop that carries more whole streams than this is a backward one (forward four, backward seven)
FORWARD_CARRIES_AT_MOST = 5


def loop_events(run: Dict[str, Any]) -> Optional[Tuple[int, List[Tuple[bool, float]]]]:
    """(whole executions of the step program on chip 0, [(is backward, ns)] of
    the scan's loops inside them), or None where there is no trace, no such
    execution, no such loop or a configuration whose family has no such mixer."""
    cfg = run.get("config") or {}
    shapes_of = getattr(family_flops.load(cfg), "gdn_scan_shapes", None)
    found = moe_trace.events_in_whole_steps(run)
    if found is None or shapes_of is None:
        return None
    seq_len = references.load(cfg["family"]).sizes(cfg)["seq_len"]
    state, stream = shapes_of(cfg, run["tokens_per_step"] // seq_len, seq_len)
    n_steps, ops = found
    hits = []
    for e in ops:
        shapes = kda_trace.carried(e.name)
        if state in shapes:
            whole = sum(len(s) == 3 and s[:2] == stream for s in shapes)
            hits.append((whole > FORWARD_CARRIES_AT_MOST, e.dur_ns))
    return (n_steps, hits) if hits else None
