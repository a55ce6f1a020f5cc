"""A configuration's own FLOP and byte arithmetic, found by its ``family``:
the module ``benchmark.flops_<family>``, as ``references.load`` finds its
plain reference. A reader that works for any configuration which brings such a
module (``step.mfu_model``, ``attention.roofline``) asks here and gives
nothing where there is none."""

from __future__ import annotations

import importlib
from types import ModuleType
from typing import Any, Dict, Optional


def load(cfg: Dict[str, Any]) -> Optional[ModuleType]:
    """``benchmark.flops_<family>`` of the configuration, or None."""
    try:
        return importlib.import_module(f"benchmark.flops_{cfg.get('family')}")
    except ImportError:
        return None
