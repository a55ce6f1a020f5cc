#!/usr/bin/env python3
"""The stamp's error: in a profiler trace of a solo cell, how far the end of
the watcher's ``dvc:loop.step_done`` annotation (the program's ``d``, when
``block_until_ready`` returned on the watcher thread) lies behind the end of
the step program on chip 0 that it waited for.

    python experiments/step_done_in_trace.py .bench_work/medium-solo

Each annotation is paired with the last execution of the step's program that
ended before it did; prints the pairs' count, the median and the largest
difference in microseconds, and the same for the annotations that really
waited (began before the program ended: the others found their step done, the
watcher was behind).
"""

import glob
import json
import os
import re
import statistics
import sys


def main() -> int:
    from jax.profiler import ProfileData

    where = sys.argv[1]
    program = re.compile(sys.argv[2] if len(sys.argv) > 2 else r"^jit_(step|multi|grad_step)\(")
    if os.path.isdir(where):
        files = sorted(glob.glob(os.path.join(where, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
        if not files:
            print(f"no .xplane.pb under {where}", file=sys.stderr)
            return 1
        where = files[-1]
    data = ProfileData.from_file(where)
    done, runs = [], []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name == "/host:CPU":
                done += [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events if e.name == "dvc:loop.step_done"]
            elif plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                runs += [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events if program.search(e.name)]
    runs.sort()
    pairs = []
    for a, b in sorted(done):
        ended = [r for r in runs if r[1] <= b]
        if ended:
            pairs.append({"lag_us": (b - ended[-1][1]) / 1e3, "waited": a < ended[-1][1]})
    if not pairs:
        print(json.dumps({"file": where, "step_done": len(done), "program_runs": len(runs), "pairs": 0}))
        return 1
    out = {"file": where, "step_done": len(done), "program_runs": len(runs)}
    for name, sel in (("all", pairs), ("waited", [p for p in pairs if p["waited"]])):
        lags = [p["lag_us"] for p in sel]
        out[name] = {"pairs": len(lags), "median_us": statistics.median(lags) if lags else None,
                     "max_us": max(lags) if lags else None}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
