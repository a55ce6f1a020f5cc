"""loop.wait_over_trace_idle (ratio): inside the traced round's interval, the
waits the PROGRAM stamped (``loop.chip_wait`` spans, late and held, clipped to
the interval) over the idle time the DEVICE TRACE shows on chip 0 (the quantity
of ``loop.round_block_ms``). The check on the instrument: near 1 the program's
two stamps a step account for the chip's idle time; far from 1 the stamps or
the clocks are off. The spans are put on the trace's clock through the
``bench:averager_call`` mark and that round's ``wall0``; nothing where that
cannot be done. Layer: train loop. Moves round_tok_s_chip."""

from benchmark import chip_timeline


def compute(run):
    interval = chip_timeline.traced_interval_on_the_spans_clock(run)
    idle_s = chip_timeline.trace_idle_s(run)
    waits = chip_timeline.waits(run) if interval and idle_s else None
    if waits is None:
        return None
    t0, t1 = interval
    inside = sum(max(0.0, min(t1, w["t0"] + w["wait_s"]) - max(t0, w["t0"])) for w in waits)
    return inside / idle_s
