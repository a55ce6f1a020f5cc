"""loop.late_ms (ms): what the chip waited for the HOST, per launch-to-launch
period: it had finished a step and the next was not enqueued. Layer: train
loop. Moves round_tok_s_chip.

From the program's own two stamps a step (``late = max(0, enqueued - the step
before done)``, exact, no model): ``late_s`` summed over the window's
``loop.steps`` spans, less what fell into the harness's own hook (the probe's
syncs and profiler calls, ``chip_timeline.hook_waits``), over the whole periods
the window held."""

from benchmark import chip_timeline


def compute(run):
    spans = chip_timeline.stretches(run)
    return chip_timeline.ms_a_period(run, sum(s["late_s"] - s["hook_s"] for s in spans) if spans else None)
