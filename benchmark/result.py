"""The last line of a run: one JSON object with exactly the contract's keys."""

from __future__ import annotations

import json
import math
from typing import Any, Dict, Optional


def build(correct: bool, attempted: int, failed: int,
          metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
          breakdown: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """``metrics`` maps a name to ``{"value": number, "unit": str}``. A value
    that is not a finite number is a bug in a reader, never a result."""
    for name, m in metrics.items():
        v = m["value"]
        if isinstance(v, bool) or not isinstance(v, (int, float)) or not math.isfinite(v):
            raise ValueError(f"metric {name} has no finite value: {v!r}")
    line: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": m["value"], "unit": m["unit"]} for k, m in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = {
            "device_ops": [[str(n), float(s)] for n, s in breakdown.get("device_ops", [])][:10],
            "idle_gaps": [[str(n), float(s)] for n, s in breakdown.get("idle_gaps", [])][:10],
        }
    return line


def dumps(line: Dict[str, Any]) -> str:
    return json.dumps(line, separators=(", ", ": "))
