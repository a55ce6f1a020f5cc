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
"""

import collections
import glob
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import trace  # noqa: E402

SHOWN_FROM_MS = 0.02


def main(work_dir: str, program: str = r"^jit_step(\(|$)") -> None:
    files = sorted(glob.glob(os.path.join(work_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not files:
        sys.exit(f"no .xplane.pb under {work_dir}: run the cell with --trace 1 first")
    tr = trace.Trace.from_xplane(files[-1])
    steps = sorted(trace.program_runs(tr, program), key=lambda e: e.dur_ns)
    if not steps:
        sys.exit(f"no execution of {program} in {files[-1]}")
    step = steps[len(steps) // 2]
    print(f"== {len(steps)} steps, median {step.dur_ns / 1e6:.3f} ms")
    ops = trace._ops(tr.device_planes()[0])
    inside = [e for e in ops if step.start_ns <= e.start_ns and e.end_ns <= step.end_ns]
    by_label = collections.defaultdict(lambda: [0, 0.0])
    for e, own_ns, leaf in trace.self_times(inside):
        label = re.sub(r"\.\d+( |$)", r"\1", trace.op_label(e.name))
        by_label[label][0] += 1
        by_label[label][1] += own_ns
        if own_ns / 1e6 >= SHOWN_FROM_MS:
            print(f"{(e.start_ns - step.start_ns) / 1e6:9.3f} {own_ns / 1e6:8.3f} ms{'' if leaf else ' (own)'}  {e.name[:150]}")
    print("== by label")
    for label, (n, ns) in sorted(by_label.items(), key=lambda kv: -kv[1][1]):
        if ns / 1e6 >= SHOWN_FROM_MS:
            print(f"{ns / 1e6:9.3f} ms x{n:<4d} {label}")


if __name__ == "__main__":
    main(*sys.argv[1:3])
