#!/usr/bin/env python
"""Every operation of one whole step in a traced benchmark run, with its own
milliseconds: the table PERF.md section 5's breakdowns are cut from.

    python3 benchmark/run.py --workload lfm2-solo-8k --seed 7 --seconds 45 --trace 1
    python experiments/step_ops_in_trace.py .bench_work/lfm2-solo-8k [step program regex] > chiprun_out/ops.txt

Reads the newest ``.xplane.pb`` under the cell's work directory with the
benchmark's own trace reader, takes the MEDIAN whole execution of the step's
program on chip 0 and prints each operation inside it by start time (a loop
spans its body: ``own`` is without its children), then the same operations
summed by label (name without its number, and the type of its result).

Where ``step_scopes.json`` stands beside the trace (the benchmark's ``scope.*``
readers leave the program's scope map in the cell's work directory, a
``DVC_PROFILE_DIR`` run of a volunteer beside its profile: give that directory
instead), every line also says which of the program's scopes the operation is
under and in which pass (``fwd``, ``refwd`` = recomputed forward, ``bwd``;
``?`` = the map does not hold the instruction with this result, ``*`` = a
fusion that swallowed instructions of more than one group), a third table
sums the step by group and pass, and a fourth the labels inside each.
"""

import collections
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import scope_trace, trace  # noqa: E402

SHOWN_FROM_MS = 0.02
SHOWN_SHARE = 0.005  # of the step, in the table by scope, pass and label


def main(work_dir: str, program: str = r"^jit_step(\(|$)") -> None:
    files = sorted(glob.glob(os.path.join(work_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not files:
        sys.exit(f"no .xplane.pb under {work_dir}: run the cell with --trace 1 first")
    tr = trace.Trace.from_xplane(files[-1])
    scopes = None
    if os.path.exists(os.path.join(work_dir, "step_scopes.json")):
        with open(os.path.join(work_dir, "step_scopes.json")) as fh:
            scopes = json.load(fh)
    steps = sorted(trace.program_runs(tr, program), key=lambda e: e.dur_ns)
    if not steps:
        sys.exit(f"no execution of {program} in {files[-1]}")
    step = steps[len(steps) // 2]
    print(f"== {len(steps)} steps, median {step.dur_ns / 1e6:.3f} ms")
    ops = trace._ops(tr.device_planes()[0])
    inside = [e for e in ops if step.start_ns <= e.start_ns and e.end_ns <= step.end_ns]
    by_label = collections.defaultdict(lambda: [0, 0.0])
    by_scope = collections.defaultdict(lambda: collections.defaultdict(float))
    by_scope_label = collections.defaultdict(lambda: [0, 0.0])
    for e, own_ns, leaf in trace.self_times(inside):
        label = re.sub(r"\.\d+( |$)", r"\1", trace.op_label(e.name))
        by_label[label][0] += 1
        by_label[label][1] += own_ns
        where = ""
        if scopes is not None:
            rec, _ = scope_trace.resolve(scopes, e.name)
            if rec is None:
                group, which, where = "unresolved", "?", f"{'?':<12s} {'?':<5s} "
            else:
                group, which = scopes["vocabulary"].get(rec["scope"] or "", "other"), rec["pass"]
                where = f"{(rec['scope'] or '-') + ('*' if rec['mixed'] else ''):<12s} {which:<5s} "
            by_scope[group][which] += own_ns
            by_scope_label[(group, which, label)][0] += 1
            by_scope_label[(group, which, label)][1] += own_ns
        if own_ns / 1e6 >= SHOWN_FROM_MS:
            print(f"{(e.start_ns - step.start_ns) / 1e6:9.3f} {own_ns / 1e6:8.3f} ms{'' if leaf else ' (own)'}  {where}{e.name[:150]}")
    print("== by label")
    for label, (n, ns) in sorted(by_label.items(), key=lambda kv: -kv[1][1]):
        if ns / 1e6 >= SHOWN_FROM_MS:
            print(f"{ns / 1e6:9.3f} ms x{n:<4d} {label}")
    if scopes is None:
        print(f"== no step_scopes.json in {work_dir}: no scopes (a traced benchmark run of this tree leaves one)")
        return
    print(f"== by scope ({scopes['program']}; group: ms in all passes = fwd + refwd + bwd)")
    for group, passes in sorted(by_scope.items(), key=lambda kv: -sum(kv[1].values())):
        parts = " + ".join(f"{passes.get(p, 0.0) / 1e6:.3f}" for p in (("?",) if group == "unresolved" else ("fwd", "refwd", "bwd")))
        print(f"{sum(passes.values()) / 1e6:9.3f} ms  {group:<10s} = {parts}")
    print(f"== by scope, pass and label (from {SHOWN_SHARE:.1%} of the step)")
    for (group, which, label), (n, ns) in sorted(by_scope_label.items(), key=lambda kv: -kv[1][1]):
        if ns >= SHOWN_SHARE * step.dur_ns:
            print(f"{ns / 1e6:9.3f} ms x{n:<4d} {group:<10s} {which:<5s} {label}")


if __name__ == "__main__":
    main(*sys.argv[1:3])
