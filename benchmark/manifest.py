"""``BENCHMARK.json`` and the data files a cell's entry names.

The harness is driven by data: a configuration, a traffic mix and a
per-layer metric are each one file, found by the name the manifest gives.
Nothing here knows a particular model or mix.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """The manifest or a file it names is missing or malformed."""


def _read_json(path: str) -> Any:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


class Manifest:
    """The parsed manifest plus the directory its data files live under."""

    def __init__(self, root: str = REPO_ROOT, bench_dir: Optional[str] = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "benchmark")
        self.doc: Dict[str, Any] = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.run_seconds = int(self.doc["run_seconds"])

    # -- entries ----------------------------------------------------------

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r}; known: {[w['name'] for w in self.doc['workloads']]}"
        )

    def config_entry(self, name: str) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise ManifestError(f"no configuration {name!r} in the manifest")

    def metrics_for(self, cell: str, kind: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics ``cell`` reports: those
        with no ``workloads`` key, and those that list the cell."""
        return [
            m for m in self.doc[kind]
            if "workloads" not in m or cell in m["workloads"]
        ]

    # -- files ------------------------------------------------------------

    def config_path(self, name: str) -> str:
        """A manifest configuration's ``file``; for a name the manifest does
        not list (a rehearsal), ``configs/<name>.json``."""
        for c in self.doc["configs"]:
            if c["name"] == name:
                return os.path.join(self.root, c["file"])
        return os.path.join(self.bench_dir, "configs", f"{name}.json")

    def load_config(self, name: str) -> Dict[str, Any]:
        return _read_json(self.config_path(name))

    def load_traffic(self, name: str) -> Dict[str, Any]:
        return _read_json(os.path.join(self.bench_dir, "traffic", f"{name}.json"))

    def layer_metric_path(self, name: str) -> str:
        """``layer_metrics/<name>.json`` (a declaration) or ``<name>.py`` (a
        reader with one ``compute(run)``); the declaration wins."""
        base = os.path.join(self.bench_dir, "layer_metrics", name)
        for ext in (".json", ".py"):
            if os.path.exists(base + ext):
                return base + ext
        raise ManifestError(f"no reader for per-layer metric {name!r} under {base}.*")

    # -- checks -----------------------------------------------------------

    def check(self) -> None:
        """Every name resolves and is well formed. Raises ``ManifestError``."""
        doc = self.doc
        names: List[str] = []
        for kind in ("end_to_end", "per_layer"):
            for m in doc[kind]:
                names.append(m["name"])
                if not NAME_RE.match(m["name"]):
                    raise ManifestError(f"bad metric name {m['name']!r}")
                if not UNIT_RE.match(m["unit"]):
                    raise ManifestError(f"bad unit {m['unit']!r} on {m['name']}")
                if m["better"] not in ("lower", "higher"):
                    raise ManifestError(f"bad 'better' on {m['name']}")
                if m["source"] not in SOURCES:
                    raise ManifestError(f"bad source on {m['name']}")
                for w in m.get("workloads", ()):
                    self.cell(w)
        if len(set(names)) != len(names):
            raise ManifestError("two metrics share a name")
        cells = [w["name"] for w in doc["workloads"]]
        e2e = {m["name"]: set(m.get("workloads", cells)) for m in doc["end_to_end"]}
        for m in doc["per_layer"]:
            if m["moves"] not in e2e:
                raise ManifestError(f"{m['name']} moves unknown metric {m['moves']!r}")
            # A per-layer metric is reported only where the metric it moves is.
            stray = set(m.get("workloads", cells)) - e2e[m["moves"]]
            if stray:
                raise ManifestError(
                    f"{m['name']} moves {m['moves']}, which {sorted(stray)} do not report")
            self.layer_metric_path(m["name"])
        for c in doc["configs"]:
            if not NAME_RE.match(c["name"]):
                raise ManifestError(f"bad configuration name {c['name']!r}")
            self.load_config(c["name"])
        for w in doc["workloads"]:
            for key in ("name", "config", "traffic"):
                if not NAME_RE.match(w[key]):
                    raise ManifestError(f"bad {key} {w[key]!r}")
            self.config_entry(w["config"])
            self.load_traffic(w["traffic"])
            if w["chips"] not in (1, 4):
                raise ManifestError(f"{w['name']}: chips must be 1 or 4")
