"""The state-space scan's kernels in a device trace, for the ``ssm.*``
readers. An ``XLA Ops`` event is named by its instruction's text, which starts
with the kernel's name: ``%dvc_ssd_fwd.N`` / ``%dvc_ssd_bwd.N``
(``ops/ssd.py``; a later kernel under a ``dvc_ssd_`` name whose name ends in
``fwd`` or ``bwd`` is read with them). A program without such a kernel (every
model without a state-space mixer, the plain form on the CPU or across chips,
the parent of PR 48) gives nothing."""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import moe_trace, trace

KERNEL_RE = re.compile(r"^dvc_ssd_(?:\w*_)?(fwd|bwd)")


def kernel_events(run: Dict[str, Any]) -> Optional[Tuple[int, List[Tuple[bool, float]]]]:
    """(whole executions of the step program on chip 0, [(is backward, ns)] of
    the scan's kernels inside them), or None where there is no trace, no such
    execution or no such kernel."""
    found = moe_trace.events_in_whole_steps(run)
    if found is None:
        return None
    n_steps, ops = found
    hits = [(m.group(1) == "bwd", e.dur_ns) for e in ops
            for m in [KERNEL_RE.match(trace.op_name(e.name))] if m]
    return (n_steps, hits) if hits else None
