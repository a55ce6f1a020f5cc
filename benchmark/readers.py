"""Per-layer metrics: one small reader per metric, found by the metric's name.

``layer_metrics/<name>.json`` declares where the number comes from;
``layer_metrics/<name>.py`` holds one ``compute(run)`` where a declaration
cannot say it. A reader that finds nothing to read returns None and the
metric is left out of the line.

The ``run`` a reader sees is a dict:

- ``stats``: flat counters (``compile.setup_seconds``, ``codec.degraded``,
  ``memory.peak_bytes``, ...), differences over the window where that is what
  the name says;
- ``spans``: the telemetry spans that started inside the window
  (``{"trace", "name", "t0", "dur_s", "attrs"}``);
- ``rounds``: the calls into the averager that were launched and merged inside
  the window;
- ``trace``: the ``benchmark.trace.Trace`` of a traced run, else None;
  ``step_program``: a regex for the train step's program name in it;
- ``tokens_per_step``, ``flops_per_token``, ``chips``, ``peak`` (the peak
  table's entry), ``config``, ``traffic``.

A declaration's ``read`` is one of:

- ``{"stat": key, "plus": [keys...], "scale": x}``;
- ``{"spans": [names...], "reduce": "median_per_round"}``: per round (trace
  id) the summed duration of the named spans, then the median over rounds;
- ``{"program": regex, "reduce": "median_ms" | "sum_ms"}`` over the
  executions of the matching compiled programs in the traced window; the
  regex ``$step`` stands for the train step's program.
"""

from __future__ import annotations

import importlib.util
import json
import statistics
from typing import Any, Dict, Optional

from benchmark import trace as tr


def _stat(run: Dict[str, Any], read: Dict[str, Any]) -> Optional[float]:
    vals = [run["stats"].get(k) for k in [read["stat"], *read.get("plus", [])]]
    if any(v is None for v in vals):
        return None
    return float(sum(vals)) * float(read.get("scale", 1.0))


def spans_per_round(spans, names) -> Dict[str, float]:
    """Round (trace id) -> summed seconds of the spans called one of ``names``."""
    out: Dict[str, float] = {}
    for s in spans:
        if s["name"] in names and s.get("dur_s") is not None:
            out[s["trace"]] = out.get(s["trace"], 0.0) + float(s["dur_s"])
    return out


def _spans(run: Dict[str, Any], read: Dict[str, Any]) -> Optional[float]:
    if read.get("reduce", "median_per_round") != "median_per_round":
        raise ValueError(f"unknown span reduction {read['reduce']!r}")
    per_round = spans_per_round(run["spans"], set(read["spans"]))
    if not per_round:
        return None
    return statistics.median(per_round.values()) * float(read.get("scale", 1.0))


def _program(run: Dict[str, Any], read: Dict[str, Any]) -> Optional[float]:
    if run.get("trace") is None:
        return None
    pattern = run["step_program"] if read["program"] == "$step" else read["program"]
    runs = tr.program_runs(run["trace"], pattern)
    if not runs:
        return None
    how = read.get("reduce", "median_ms")
    if how == "median_ms":
        return statistics.median(e.dur_ns for e in runs) / 1e6
    if how == "sum_ms":
        return sum(e.dur_ns for e in runs) / 1e6
    raise ValueError(f"unknown program reduction {how!r}")


def compute(path: str, run: Dict[str, Any]) -> Optional[float]:
    """The metric whose reader is the file ``path``."""
    if path.endswith(".json"):
        with open(path) as fh:
            read = json.load(fh)["read"]
        if "stat" in read:
            return _stat(run, read)
        if "spans" in read:
            return _spans(run, read)
        if "program" in read:
            return _program(run, read)
        raise ValueError(f"{path}: a declaration reads 'stat', 'spans' or 'program'")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + str(abs(hash(path))), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.compute(run)
    return None if value is None else float(value)
