"""The block-diffusion attention kernels in a device trace, for the
``attention.bd_*`` readers.

An ``XLA Ops`` event is named by its instruction's text, which starts with the
kernel's name: ``%dvc_flash_bd_fwd.N`` / ``%dvc_flash_bd_bwd.N``
(``ops/pallas_attention.py``: the calls under the three-part mask of
``models/sdar_moe.py``). A program without such a kernel (every model but this
one, and the parent of PR 60) gives nothing."""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import moe_trace, trace

KERNEL_RE = re.compile(r"^dvc_flash_bd_(fwd|bwd)")


def kernel_events(run: Dict[str, Any]) -> Optional[Tuple[int, List[Tuple[bool, float]]]]:
    """(whole executions of the step program on chip 0, [(is backward, ns)] of
    the block-diffusion kernels inside them), or None where there is no trace,
    no such execution or no such kernel."""
    found = moe_trace.events_in_whole_steps(run)
    if found is None:
        return None
    n_steps, ops = found
    hits = [(m.group(1) == "bwd", e.dur_ns) for e in ops
            for m in [KERNEL_RE.match(trace.op_name(e.name))] if m]
    return (n_steps, hits) if hits else None


def route_span_attribute(run: Dict[str, Any], key: str) -> List[float]:
    """``key`` of every ``moe.route`` span of the window that carries it."""
    return [float((s.get("attrs") or {})[key]) for s in run["spans"]
            if s["name"] == "moe.route" and key in (s.get("attrs") or {})]
