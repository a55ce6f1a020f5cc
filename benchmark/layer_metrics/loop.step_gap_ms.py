"""loop.step_gap_ms (ms): median idle gap on chip 0 between consecutive
executions of the train step's program. Layer: train loop. Moves tok_s_chip.

What the host loop (data fetch, dispatch, hooks) adds to every step when it
cannot keep the device's queue full."""

import statistics

from benchmark import trace


def compute(run):
    if run.get("trace") is None:
        return None
    gaps = trace.gaps_between(trace.program_runs(run["trace"], run["step_program"]))
    return statistics.median(gaps) / 1e6 if gaps else None
