"""loop.held_ms (ms): what steps took beyond their own time with the step
already enqueued, per launch-to-launch period: a step sat in the chip's
in-order queue behind something else (a placement, a codec program, the merge)
or ran slow. Layer: train loop. Moves round_tok_s_chip.

From the program's own two stamps a step (``own = done - max(enqueued, the
step before done)`` less its running median, where that is over the program's
thresholds): ``held_s`` summed over the window's ``loop.steps`` spans, over the
whole periods the window held."""

from benchmark import chip_timeline


def compute(run):
    spans = chip_timeline.stretches(run)
    return chip_timeline.ms_a_period(run, sum(s["held_s"] for s in spans) if spans else None)
