"""loop.step_wall_max_over_median (x): the longest step of the window by the
wall (done to done, the program's own stamps) over the median step: the
largest ``step_s_max`` of the window's ``loop.steps`` spans over the median of
their ``step_s_p50``. 1.0x in an undisturbed run; ten or more in a run that
stalled for seconds inside its window (PERF.md section 7: PR 41, PR 46), which
the end-to-end number alone reads as a slow program. Where the harness's own
hook stopped the chip inside a stretch (``chip_timeline.hook_waits``: the
probe's syncs, a traced run's profiler start and stop, 110 s on four chips)
that stretch's longest step is taken less the hook's seconds, and no shorter
than its median. Layer: train loop. Moves tok_s_chip."""

import statistics

from benchmark import chip_timeline


def compute(run):
    spans = chip_timeline.stretches(run)
    if not spans:
        return None
    longest = max(max(s["step_s_p50"], s["step_s_max"] - s["hook_s"]) for s in spans)
    return longest / statistics.median(s["step_s_p50"] for s in spans)
