"""step.mfu (%): model FLOPs of one step over the step program's device time
at the chips' published peak. Layer: compiled step. Moves tok_s_chip.

FLOPs a token: 6 N + 12 L T d (benchmark/flops.py), recomputation not
counted; the time is the median device duration of the train step's program,
so host gaps between steps do not enter (they are loop.step_gap_ms)."""

import statistics

from benchmark import flops, trace


def compute(run):
    if run.get("trace") is None or run.get("peak") is None:
        return None
    runs = trace.program_runs(run["trace"], run["step_program"])
    if not runs:
        return None
    step_s = statistics.median(e.dur_ns for e in runs) / 1e9
    return flops.mfu_percent(
        run["tokens_per_step"], run["flops_per_token"], step_s,
        run["chips"], run["peak"]["bf16_flops"],
    )
