#!/usr/bin/env python
"""What runs inside the share's chunk loops in a traced benchmark run.

    python3 benchmark/run.py --workload smallthinker-solo-16k --seed 7 --seconds 45 --trace 1
    python experiments/share_loops_in_trace.py .bench_work/smallthinker-solo-16k 196608 > chiprun_out/loops.txt

Reads the newest ``.xplane.pb`` under the cell's work directory with the
benchmark's own trace reader. The chunk loops are found as
``benchmark/layer_metrics/moe.share_device_ms.py`` finds them (a ``%while.N``
whose carried tuple holds a vector over at least the S x k assignments, the
second argument: 196,608 in smallthinker-solo-16k, 262,144 in laguna-solo-8k);
for each, its runs on chip 0 with their median length, and every operation
inside the median run with its own milliseconds (the grouped matmuls are
``gmm`` / ``jvp_jit_gmm__`` / ``transpose_jvp_jit_[t]gmm___``, the row
gathers nameless ``fusion.N`` told by their result's type, ``bf16[rows,d]``).
Since PR 38 a token's run is summed by the one ``fusion.N`` whose result is
``bf16[rows/128,128,d]`` (the batched 0/1 product of ``_combine``, a
convolution fusion: 1.62 ms over ``[104448,2560]``), with
``multiply_reduce_fusion.N f32[rows/128-1,d]`` (the carry over a tile's edge, 0.10
ms) in front of it; before it they were three ``slice_select_fusion`` of
``bf16[rows-1|2|4,d]`` and three ``pad_add_fusion`` of ``bf16[rows,d]`` a loop.
PERF.md section 5 quotes the listing for the two share cells (PR 36, PR 38).
"""

import collections
import glob
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import trace  # noqa: E402

SHOWN_FROM_MS = 0.05


def main(work_dir: str, rows: int) -> None:
    files = sorted(glob.glob(os.path.join(work_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not files:
        sys.exit(f"no .xplane.pb under {work_dir}: run the cell with --trace 1 first")
    spec = importlib.util.spec_from_file_location(
        "share_device_ms", os.path.join(REPO, "benchmark", "layer_metrics", "moe.share_device_ms.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ops = trace._ops(trace.Trace.from_xplane(files[-1]).device_planes()[0])
    loops = collections.defaultdict(list)
    for e in ops:
        if reader.carries_assignments(e.name, rows):
            loops[trace.op_name(e.name)].append(e)
    for name, runs in loops.items():
        runs.sort(key=lambda e: e.dur_ns)
        median = runs[len(runs) // 2]
        print(f"== {name}: {len(runs)} runs, median {median.dur_ns / 1e6:.3f} ms, "
              f"{runs[0].dur_ns / 1e6:.3f} to {runs[-1].dur_ns / 1e6:.3f}")
        inside = [e for e in ops if e is not median and median.start_ns <= e.start_ns and e.end_ns <= median.end_ns]
        for e, own_ns, leaf in trace.self_times(inside):
            if leaf and own_ns / 1e6 >= SHOWN_FROM_MS:
                print(f"   {own_ns / 1e6:8.3f}  {trace.op_label(e.name)[:100]}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
