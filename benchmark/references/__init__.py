"""Plain references, one module per model family (``<family>.py``), found by
the ``family`` a configuration file names."""

from __future__ import annotations

import importlib
from types import ModuleType


def load(family: str) -> ModuleType:
    return importlib.import_module(f"benchmark.references.{family}")
