#!/usr/bin/env python
"""Every collective of the step in a traced benchmark run, not a top ten.

    python3 benchmark/run.py --workload large-solo-4chip --seed 7 --seconds 45 --trace 1
    python experiments/collectives_in_trace.py .bench_work/large-solo-4chip --out chiprun_out/collectives.json

Reads the newest ``.xplane.pb`` under the cell's work directory with the
benchmark's own trace reader and lists, for chip 0 inside the traced window,
each collective operation by instruction name: the type of its result, calls a
step, bytes of its result, milliseconds a step on the instruction stream
(``XLA Ops``) or beside it (``Async XLA Ops``: start/done pairs), and what
share of the step that is. Operations of the same kind and result type inside
the scanned bodies differ only by their number; each is listed.
"""

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import trace as trace_mod  # noqa: E402

_ITEM = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1}


def result_types(text: str):
    """The array types of an instruction's result, a tuple's elements each."""
    head = text.split("=", 1)[1] if "=" in text else text
    head = re.split(r"\s(?:all-|collective-|reduce-scatter|ragged-)", head, 1)[0]
    return re.findall(r"([a-z0-9]+)\[([0-9,]*)\]", head)


def n_bytes(types) -> int:
    total = 0
    for dtype, dims in types:
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _ITEM.get(dtype, 4)
    return total


def collectives(trace: trace_mod.Trace, step_pattern: str = r"^jit_step(\(|$)"):
    window = trace.window()
    plane = trace.device_planes()[0]
    steps = trace_mod.program_runs(trace, step_pattern)
    rows = {}
    for line in (trace_mod.OPS_LINE, trace_mod.ASYNC_OPS_LINE):
        for e in trace_mod._ops(plane, line):
            if not trace_mod.is_collective(e.name):
                continue
            if e.start_ns < window[0] or e.end_ns > window[1]:
                continue
            types = result_types(e.name)
            row = rows.setdefault((trace_mod.op_name(e.name), line), {
                "op": trace_mod.op_name(e.name), "line": line,
                "result": ", ".join(f"{t}[{d}]" for t, d in types)[:120],
                "bytes": n_bytes(types), "calls": 0, "seconds": 0.0,
            })
            row["calls"] += 1
            row["seconds"] += e.dur_ns / 1e9
    n = max(1, len(steps))
    step_ms = sum(e.dur_ns for e in steps) / n / 1e6
    out = []
    for row in sorted(rows.values(), key=lambda r: -r["seconds"]):
        row["calls_per_step"] = row["calls"] / n
        row["ms_per_step"] = row["seconds"] * 1e3 / n
        row["share_of_step"] = row["ms_per_step"] / step_ms if step_ms else None
        out.append(row)
    return {"steps": len(steps), "step_ms": step_ms, "collectives": out}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("work_dir", help="a cell's .bench_work/<cell> directory, or an .xplane.pb")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    path = args.work_dir
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        if not files:
            print(f"no .xplane.pb under {path}", file=sys.stderr)
            return 1
        path = files[-1]
    trace = trace_mod.Trace.from_xplane(path)
    if not trace.device_planes():
        print(f"{path} holds no device plane (a CPU run)", file=sys.stderr)
        return 1
    doc = collectives(trace)
    doc["file"] = path
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1)
    print(f"{doc['steps']} steps of {doc['step_ms']:.2f} ms on chip 0; ms a step, calls a step, bytes, op")
    for r in doc["collectives"]:
        print(f"{r['ms_per_step']:9.3f} {r['calls_per_step']:7.1f} {r['bytes']:12d}  "
              f"{r['op']} [{'async' if r['line'] != trace_mod.OPS_LINE else 'sync'}] {r['result']}")
    sync = sum(r["ms_per_step"] for r in doc["collectives"] if r["line"] == trace_mod.OPS_LINE)
    print(f"on the instruction stream: {sync:.2f} ms a step")
    return 0


if __name__ == "__main__":
    sys.exit(main())
