#!/usr/bin/env python3
"""One run of a benchmark cell with the program's timeline of the chip's queue
written out beside the result line (``run.py`` prints neither the volunteer's
summary nor its spans):

    chiprun -- python experiments/chip_timeline_run.py --out <dir> --tag <name> \
        [--held-min S --held-share X --late-span S] -- --workload medium-round --seed 7 --seconds 45 --trace 1

Everything after ``--`` goes to ``benchmark/run.py`` unchanged, in this
process. ``chiprun_out/<dir>/<name>.timeline.json`` then holds every
``loop.steps`` span, every ``loop.chip_wait`` span as ``telemetry.chip_waits``
resolves it, the ``chip`` summary and ``swarm.chip_wait_seconds_total``. The
three options set the thresholds of ``swarm/telemetry.py`` for THIS run (0:
every excess over the running median becomes a span), which is how they were
chosen: run a cell's undisturbed window with them at 0 and read what its held
waits would have been.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    argv = sys.argv[1:]
    cut = argv.index("--") if "--" in argv else len(argv)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--held-min", type=float)
    ap.add_argument("--held-share", type=float)
    ap.add_argument("--late-span", type=float)
    args = ap.parse_args(argv[:cut])

    from distributedvolunteercomputing_tpu.swarm import telemetry
    from distributedvolunteercomputing_tpu.swarm import volunteer as volunteer_mod

    for name, value in (("HELD_MIN_S", args.held_min), ("HELD_SHARE", args.held_share),
                        ("LATE_SPAN_S", args.late_span)):
        if value is not None:
            setattr(telemetry, name, value)
    made = []
    init = volunteer_mod.Volunteer.__init__

    def keeping(self, *a, **kw):
        made.append(self)
        init(self, *a, **kw)

    volunteer_mod.Volunteer.__init__ = keeping
    from benchmark import run as bench_run

    rc = bench_run.main(argv[cut + 1:])
    out_dir = os.path.join(REPO, "chiprun_out", args.out)
    os.makedirs(out_dir, exist_ok=True)
    doc = {"rc": rc, "thresholds": {k: getattr(telemetry, k) for k in ("LATE_SPAN_S", "HELD_MIN_S", "HELD_SHARE")}}
    if made:
        tele = made[0].telemetry
        spans = tele.tracer.spans()
        doc["chip"] = tele.chip()
        doc["steps"] = [s for s in spans if s["name"] == "loop.steps"]
        doc["waits"] = [{**w, "t0": s["t0"], **({"own_s": s["attrs"]["own_s"]} if "own_s" in s["attrs"] else {})}
                        for s, w in zip(spans, telemetry.chip_waits(spans)) if w is not None]
        doc["counter"] = tele.registry.counter("swarm.chip_wait_seconds_total")._scrape()["values"]
        doc["work_spans"] = [s for s in spans if s["name"] in telemetry.CHIP_WORK_SPANS]
    with open(os.path.join(out_dir, f"{args.tag}.timeline.json"), "w") as fh:
        json.dump(doc, fh)
    by = {}
    for w in doc.get("waits", ()):
        key = f"{w['kind']}/{w['during']}"
        by[key] = (by.get(key, (0, 0.0))[0] + 1, round(by.get(key, (0, 0.0))[1] + w["wait_s"], 6))
    print(f"timeline {args.tag}: chip {doc.get('chip')} waits by kind/during (count, s) {by}", file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
