"""loop.wait_share (%): the share of the steps' wall time in which the chip
waited by the program's own stamps: ``late_s`` + ``held_s`` over the seconds
the window's ``loop.steps`` spans cover (their own seconds and not the
window's, so a stretch the window's edge cuts distorts nothing). Stands beside
``device.idle_share``, which a profiler reads from outside in ten traced
steps; this one is read in every period of every run. What it cannot see is
the runtime's microseconds between two programs, under a host stamp's reach.
What fell into the harness's own hook (the probe's syncs, a traced run's
profiler start and stop: ``chip_timeline.hook_waits``) is taken off both the
waits and the seconds. Layer: train loop. Moves tok_s_chip."""

from benchmark import chip_timeline


def compute(run):
    spans = chip_timeline.stretches(run)
    covered = sum(s["dur_s"] - s["hook_s"] for s in spans)
    if not covered:
        return None
    return 100.0 * sum(s["late_s"] - s["hook_s"] + s["held_s"] for s in spans) / covered
