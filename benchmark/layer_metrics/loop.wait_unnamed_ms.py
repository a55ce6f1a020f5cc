"""loop.wait_unnamed_ms (ms): the chip's waits that nothing of the program
explains, per launch-to-launch period: ``loop.chip_wait`` spans of kind
``held`` that no span which puts work on the chip or its host link overlaps
(``telemetry.chip_waits`` resolves ``during`` to ``none``), and ``late`` ones
that fell into no phase of the train thread (``loop``). Meant to read near 0:
a stall with nothing of the program in it shows here. Layer: train loop. Moves
round_tok_s_chip."""

from benchmark import chip_timeline


def compute(run):
    waits = chip_timeline.waits(run)
    if waits is None:
        return None
    return chip_timeline.ms_a_period(
        run, sum(w["wait_s"] for w in waits if w["during"] in ("none", "loop")))
