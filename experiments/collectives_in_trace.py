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

Since PR 57 the sharded step is compiled with asynchronous collectives where
its mesh has a ``tp`` axis (``parallel/train_step.step_compiler_options``), and
this compiler's asynchronous all-reduce is a pair of FUSIONS on the instruction
stream, ``async-collective-start.N`` / ``async-collective-done.N``, with the
transfer's parts inside the fusions scheduled between them: no event carries an
``all-reduce`` name, so ``benchmark/trace.collective_time`` (the metrics
``device.collective_share`` / ``collective_exposed``) counts only what is still
synchronous; ``benchmark/collective_pairs.py`` (``device.collective_all_share``
/ ``collective_all_exposed``) counts the pairs too. This listing pairs them the
same way ([pair]): from the start's beginning to the done's end a call, and
beside it what the two fusions themselves took on the stream (``own``: the wait
that nothing hid).
"""

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import collective_pairs  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402

PAIR = "pair"  # beside the two lines' names: a start / done pair of fusions on the instruction stream

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
    # asynchronous pairs: start.N ... done.N on the instruction stream (benchmark/collective_pairs.py)
    in_window = [e for e in trace_mod._ops(plane, trace_mod.OPS_LINE)
                 if e.start_ns >= window[0] and e.end_ns <= window[1]]
    for start, done in collective_pairs.pairs(in_window):
        number = collective_pairs.pair_part(done.name)[1]
        types = result_types(done.name.split(" fusion(", 1)[0])
        row = rows.setdefault((f"async-collective{number}", PAIR), {
            "op": f"async-collective{number}", "line": PAIR,
            "result": ", ".join(f"{t}[{d}]" for t, d in types)[:120],
            "bytes": n_bytes(types), "calls": 0, "seconds": 0.0, "own_seconds": 0.0,
        })
        row["calls"] += 1
        row["seconds"] += (done.end_ns - start.start_ns) / 1e9
        row["own_seconds"] += (done.dur_ns + start.dur_ns) / 1e9
    n = max(1, len(steps))
    step_ms = sum(e.dur_ns for e in steps) / n / 1e6
    out = []
    for row in sorted(rows.values(), key=lambda r: -r["seconds"]):
        row["calls_per_step"] = row["calls"] / n
        row["ms_per_step"] = row["seconds"] * 1e3 / n
        row["share_of_step"] = row["ms_per_step"] / step_ms if step_ms else None
        if "own_seconds" in row:
            row["own_ms_per_step"] = row["own_seconds"] * 1e3 / n
        out.append(row)
    return {"steps": len(steps), "step_ms": step_ms, "collectives": out}


def per_chip(trace: trace_mod.Trace):
    """For every chip, ms over the traced window: the named collectives, the
    pairs' own start and done fusions, the pairs from start to done (union),
    and the part of those spans in which no operation at all ran (the gaps
    between the operations beside a transfer: no wait for the link, which is
    why ``device.collective_all_exposed`` leaves the inside of a pair out)."""
    window = trace.window()
    out = []
    for plane in trace.device_planes():
        ops = trace_mod._ops(plane)
        named = [(e.start_ns, e.end_ns) for e in ops if trace_mod.is_collective(e.name)]
        parts = [e for e in ops if collective_pairs.pair_part(e.name) is not None]
        spans = trace_mod.merge(trace_mod.clip(
            ((s.start_ns, d.end_ns) for s, d in collective_pairs.pairs(parts)), *window))
        leaves = trace_mod.merge(trace_mod.clip(
            ((e.start_ns, e.end_ns) for e, _, leaf in trace_mod.self_times(ops) if leaf), *window))
        out.append({
            "chip": plane.name,
            "named_ms": trace_mod.length(trace_mod.merge(trace_mod.clip(named, *window))) / 1e6,
            "pairs_own_ms": trace_mod.length(trace_mod.clip(((e.start_ns, e.end_ns) for e in parts), *window)) / 1e6,
            "pairs_span_ms": trace_mod.length(spans) / 1e6,
            "gaps_in_spans_ms": trace_mod.length(trace_mod.subtract(spans, leaves)) / 1e6,
            "window_ms": (window[1] - window[0]) / 1e6,
        })
    return out


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
    doc["per_chip"] = per_chip(trace)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1)
    print(f"{doc['steps']} steps of {doc['step_ms']:.2f} ms on chip 0; ms a step, calls a step, bytes, op")
    for r in doc["collectives"]:
        kind = {trace_mod.OPS_LINE: "sync", PAIR: "pair"}.get(r["line"], "async")
        own = f" (own {r['own_ms_per_step']:.3f})" if "own_ms_per_step" in r else ""
        print(f"{r['ms_per_step']:9.3f} {r['calls_per_step']:7.1f} {r['bytes']:12d}  "
              f"{r['op']} [{kind}] {r['result']}{own}")
    sync = sum(r["ms_per_step"] for r in doc["collectives"] if r["line"] == trace_mod.OPS_LINE)
    own = sum(r.get("own_ms_per_step", 0.0) for r in doc["collectives"])
    print(f"on the instruction stream: {sync:.2f} ms a step; in the pairs' own start and done fusions: {own:.2f}")
    for c in doc["per_chip"]:
        print(f"{c['chip']}: of {c['window_ms']:.1f} ms traced, named collectives {c['named_ms']:.2f}, the pairs' own "
              f"fusions {c['pairs_own_ms']:.2f}, pairs start to done {c['pairs_span_ms']:.2f} "
              f"(no operation running in them {c['gaps_in_spans_ms']:.2f})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
